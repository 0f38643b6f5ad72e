"""Cut a profiler trace down to what ``xplane.py`` reads, for a stored test
fixture: the ``XLA Ops`` and ``XLA Modules`` lines of each TPU plane and the
harness's ``bench.*`` spans on the host, over a slice of the traced time.

    python3 bench/tests/cut_trace.py <trace_dir> <out.txtpb.gz> \\
        [--start-ms 0] [--ms 400]

The trace comes from a traced run that keeps it (``bench/run.py ...
--trace 1 --keep-trace <trace_dir>``).  The output is the cut ``XSpace`` as
gzipped text proto; ``load`` reads it back as a
``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import xplane  # noqa: E402


def _keep(plane: str, line: str, event: str) -> bool:
    if xplane._DEVICE.match(plane):
        return line in (xplane.OPS_LINE, xplane.MODULES_LINE)
    return plane.startswith("/host:") and event.startswith("bench.")


def cut(pd, start_ms: float, ms: float) -> str:
    """The text proto of ``pd`` cut to [first event + start_ms, + ms)."""
    first = min(e.start_ns for p in pd.planes for ln in p.lines
                for e in ln.events if _keep(p.name, ln.name, e.name))
    lo = first + start_ms * 1e6
    hi = lo + ms * 1e6
    out = []
    for pid, p in enumerate(pd.planes):
        lines, names = [], {}
        for lid, ln in enumerate(p.lines):
            evs = [e for e in ln.events if _keep(p.name, ln.name, e.name)
                   and lo <= e.start_ns < hi]
            if not evs:
                continue
            t0 = int(min(e.start_ns for e in evs))
            body = []
            for e in evs:
                mid = names.setdefault(e.name, len(names) + 1)
                body.append(
                    f"events {{ metadata_id: {mid} offset_ps: "
                    f"{int(round((e.start_ns - t0) * 1000))} duration_ps: "
                    f"{int(round(e.duration_ns * 1000))} }}")
            lines.append(f"lines {{ id: {lid} name: {json.dumps(ln.name)} "
                         f"timestamp_ns: {t0}\n  " + "\n  ".join(body)
                         + "\n}")
        if not lines:
            continue
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{json.dumps(n)} }} }}" for n, i in names.items()]
        out.append(f"planes {{ id: {pid} name: {json.dumps(p.name)}\n"
                   + "\n".join(lines + meta) + "\n}")
    return "\n".join(out) + "\n"


def load(path: str):
    from jax.profiler import ProfileData
    with gzip.open(path, "rt") as f:
        return ProfileData.from_text_proto(f.read())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--start-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=400.0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane.find_xplane(args.trace_dir))
    with gzip.open(args.out, "wt") as f:
        f.write(cut(pd, args.start_ms, args.ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
