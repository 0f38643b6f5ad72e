"""One traced run of a cell, with what the harness does not report.

    python3 bench/tools/trace_spans.py --workload <cell> --seed <n> \\
        --seconds <s> --out <dir> [--cut <fixture.txtpb.gz> \\
        --cut-start-ms <t> --cut-ms <ms>]

Runs the cell as ``bench/run.py --trace 1`` does (its result line carries
the per-layer metrics, the program's readings among them, and the idle gaps
named down to the engine phase) and adds, from the harness's own
``RunView``: the engine's and the drain worker's counters over the window,
the steps per second of the window before and inside the traced span, how
many of each ``ficabu.*`` span the trace holds, and how many device ops
carry the sweep's ``checkpoint`` scope.  Prints them as one JSON object,
last line on standard output, and keeps the trace under ``<dir>/trace``.
``--cut`` also writes a test fixture cut from the trace
(``bench/tests/cut_spans.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tests"))


def steps_per_s(win):
    """(steps/s before the traced span, steps/s inside it)."""
    (s0, s1), (t0, t1) = win.trace_steps, win.trace_span
    if None in (s0, s1, t0, t1) or t0 <= 0 or t1 <= t0:
        return None
    return [(s0 - win.first_step) / t0, (s1 - s0) / (t1 - t0)]


def op_scopes(pt, n: int = 3):
    """Per device: leaf ops, those with an ``op_name``, those in the
    ``checkpoint`` scope, and a few of each kind's names."""
    import spans
    pat = spans.scope_re(spans.SCOPE)
    out = []
    for dev in pt.devices:
        named = [o for o in dev.ops if o.op_name]
        scoped = [o.op_name for o in named if pat.search(o.op_name)]
        out.append({"leaf_ops": len(dev.ops), "with_op_name": len(named),
                    "scoped": len(scoped),
                    "scoped_examples": sorted(set(scoped))[:n]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cut", default=None)
    ap.add_argument("--cut-start-ms", type=float, default=None)
    ap.add_argument("--cut-ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    import harness
    got = {}
    tdir = os.path.join(args.out, "trace")
    res = harness.run(args.workload, args.seed, args.seconds, True,
                      t_start=T_START, keep_trace=tdir,
                      hooks={"view": lambda v: got.update(view=v)})
    view = got["view"]
    pt = view.program_trace
    out = {"result": res, "counters": view.counters,
           "steps_per_s_untraced_traced": steps_per_s(view.window),
           "span_counts": Counter(s.name for s in pt.spans),
           "op_scopes": op_scopes(pt)}
    if args.cut:
        import cut_spans
        import spans
        import xplane
        with open(xplane.find_xplane(tdir), "rb") as f:
            space = spans.parse(f.read())
        text = cut_spans.cut(space, args.cut_start_ms, args.cut_ms)
        cut_spans.write(args.cut, text)
        out["cut_bytes"] = os.path.getsize(args.cut)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
