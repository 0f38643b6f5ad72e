"""Cut a profiler trace down to what ``bench/spans.py`` and ``xplane.py``
read, for a stored test fixture: the ``XLA Ops`` and ``XLA Modules`` lines
of each TPU plane (each op's metadata with the stat that carries its HLO
``op_name``), and the harness's ``bench.*`` and the program's
``ficabu.*`` spans with their arguments, over a slice of the traced time.

    python3 bench/tests/cut_spans.py <trace_dir> <out.txtpb.gz> \\
        [--start-ms <t>] [--ms 1000]

Without ``--start-ms`` the slice starts 20 ms before the first ``drain``
span, so it holds a sweep and, a second later, what followed it.  The
output is the cut ``XSpace`` as gzipped text proto; ``load`` reads it back
for both readers.
"""
from __future__ import annotations

import argparse
import gzip
import os
import sys
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import spans  # noqa: E402
import xplane  # noqa: E402

LEAD_NS = 20e6


def _keep_line(plane: str, line: str) -> bool:
    if xplane._DEVICE.match(plane):
        return line in (xplane.OPS_LINE, xplane.MODULES_LINE)
    return plane.startswith("/host:")


def _keep_event(plane: str, name: str) -> bool:
    return (bool(xplane._DEVICE.match(plane)) or name.startswith("bench.")
            or name.startswith(spans.PREFIX))


def _kept(space):
    """(plane, line, event, absolute start ns) of every kept event."""
    for p in space.planes:
        for ln in p.lines:
            if not _keep_line(p.name, ln.name):
                continue
            for e in ln.events:
                name = p.event_metadata[e.metadata_id].name
                if _keep_event(p.name, name):
                    yield p, ln, e, ln.timestamp_ns + e.offset_ps * 1e-3


def cut(space, start_ms: Optional[float] = None, ms: float = 1000.0) -> str:
    """The text proto of ``space`` cut to ``ms`` milliseconds from
    ``start_ms`` after its first kept event (or from 20 ms before the
    first ``drain`` span)."""
    from google.protobuf import text_format
    kept = list(_kept(space))
    if start_ms is None:
        drains = [t for p, _, e, t in kept
                  if p.event_metadata[e.metadata_id].name
                  == spans.PREFIX + "drain"]
        lo = min(drains) - LEAD_NS
    else:
        lo = min(t for *_, t in kept) + start_ms * 1e6
    hi = lo + ms * 1e6
    out = type(space)()
    for p in space.planes:
        np_ = None
        used_md, used_stats = set(), set()
        device = bool(xplane._DEVICE.match(p.name))
        op_stats = {k for k, m in p.stat_metadata.items()
                    if m.name == spans.OP_NAME_STAT}
        for ln in p.lines:
            if not _keep_line(p.name, ln.name):
                continue
            evs = [e for e in ln.events
                   if _keep_event(p.name,
                                  p.event_metadata[e.metadata_id].name)
                   and lo <= ln.timestamp_ns + e.offset_ps * 1e-3 < hi]
            if not evs:
                continue
            if np_ is None:
                np_ = out.planes.add(id=p.id, name=p.name)
            nl = np_.lines.add(id=ln.id, name=ln.name,
                               timestamp_ns=ln.timestamp_ns)
            for e in evs:
                ne = nl.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps,
                                   duration_ps=e.duration_ps)
                ne.stats.extend(s for s in e.stats
                                if not device or s.metadata_id in op_stats)
                used_md.add(e.metadata_id)
                used_stats.update(s.metadata_id for s in ne.stats)
        if np_ is None:
            continue
        for k in sorted(used_md):
            m = p.event_metadata[k]
            nm = np_.event_metadata[k]
            nm.id, nm.name = m.id, m.name
            for s in m.stats:
                if s.metadata_id in op_stats:
                    nm.stats.add().CopyFrom(s)
                    used_stats.add(s.metadata_id)
        refs = set()
        for ln in np_.lines:
            for e in ln.events:
                refs.update(s.ref_value for s in e.stats
                            if s.WhichOneof("value") == "ref_value")
        for m in np_.event_metadata.values():
            refs.update(s.ref_value for s in m.stats
                        if s.WhichOneof("value") == "ref_value")
        for k in sorted(used_stats | refs):
            if k in p.stat_metadata:
                np_.stat_metadata[k].CopyFrom(p.stat_metadata[k])
    return text_format.MessageToString(out)


def write(path: str, text: str) -> None:
    with gzip.open(path, "wt") as f:
        f.write(text)


def load(path: str) -> str:
    """The fixture's text: ``spans.parse_text`` reads it, and so does
    ``jax.profiler.ProfileData.from_text_proto`` (for ``xplane.py``)."""
    with gzip.open(path, "rt") as f:
        return f.read()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--start-ms", type=float, default=None)
    ap.add_argument("--ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    with open(xplane.find_xplane(args.trace_dir), "rb") as f:
        space = spans.parse(f.read())
    write(args.out, cut(space, args.start_ms, args.ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
