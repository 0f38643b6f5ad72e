"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness comparison
compared, beside its limit (also the last lines on standard error).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  ``--control 1`` puts the lower-precision control in
the program's place (its int8 drains; the fp8 reference read as the served
tokens); it is for calibrating the limits and is never part of a timed run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control),
                          t_start=T_START, keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
