"""The program's own spans and named scopes in a profiler trace, and the
per-layer readings they give.

The program names its host phases with ``ficabu.*`` spans
(``repro.obs.telemetry.span``): ``engine.step`` and its children on the
engine thread, ``drain`` and its children on the drain worker.  The sweep
program names its halt-checkpoint evaluations with the ``checkpoint`` scope,
which reaches each device op's HLO ``op_name``; a TPU trace keeps that as
the ``tf_op`` stat of the op's metadata (``jit(sweep)/.../checkpoint/
dot_general:``).  ``jax.profiler.ProfileData`` shows an event's own stats
but not its metadata's, so this module reads the raw ``XSpace``
(``.xplane.pb``).  Times are seconds on the profiler's clock, computed as
``xplane.py`` computes them, so spans, device ops and ``xplane.Trace``
line up.

Readings (each ``None`` where the trace or the counters hold nothing to
read, as on a program without these spans):

  * ``step_host_ms``: median self time of ``ficabu.engine.step`` less its
    ``engine.admit`` and ``engine.publish`` children;
  * ``admit_host_ms``: mean duration of ``ficabu.engine.admit``;
  * ``sweep_checkpoint_pct``: the union of ``checkpoint``-scoped leaf-op
    intervals inside ``jit_sweep`` launches over the union of those
    launches (``scoped``, which reads any scope of any program);
  * ``publish_wait_ms`` and ``drain_host_ms``: from the engine's and the
    drain worker's counters over a window (``counter_delta``).

``idle_gaps`` names each idle gap as ``xplane.Trace.idle_gaps`` does, then
appends the innermost ``ficabu.*`` span of the engine thread that covers it.
"""
from __future__ import annotations

import bisect
import re
from functools import lru_cache
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import stats
import xplane

PREFIX = "ficabu."
STEP = PREFIX + "engine.step"
ADMIT = PREFIX + "engine.admit"
PUBLISH = PREFIX + "engine.publish"
SWEEP_PROGRAM = "jit_sweep"
SCOPE = "checkpoint"
# the stat of a device op (its event's, else its metadata's) that carries
# its HLO op_name
OP_NAME_STAT = "tf_op"


class Span(NamedTuple):
    name: str
    thread: Tuple[str, int]          # (host plane, line index)
    start: float
    end: float
    args: Dict[str, Any]


class Op(NamedTuple):
    name: str
    start: float
    end: float
    op_name: str                     # '' where no stat carries it


class Device(NamedTuple):
    ops: List[Op]                    # leaf ops: they contain no other op
    modules: List[Tuple[str, float, float]]


class ProgramTrace(NamedTuple):
    spans: List[Span]
    devices: List[Device]


# -- the XSpace schema -------------------------------------------------------
@lru_cache(maxsize=1)
def _xspace_class():
    """The ``XSpace`` message class, built from the profiler's schema
    (``tsl/profiler/protobuf/xplane.proto``, the fields read here)."""
    from google.protobuf import descriptor_pb2 as D
    from google.protobuf import descriptor_pool, message_factory
    F = D.FieldDescriptorProto
    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STR, BYT, MSG = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    OPT, REP = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fd = D.FileDescriptorProto(name="bench_xplane.proto",
                               package="bench.xplane", syntax="proto3")

    def message(parent, name, fields, oneof=None):
        m = parent.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for num, fname, ftype, label, tname, in_oneof in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".bench.xplane." + tname
            if in_oneof:
                f.oneof_index = 0
        return m

    mt = fd.message_type
    message(mt, "XSpace", [(1, "planes", MSG, REP, "XPlane", 0)])
    plane = message(mt, "XPlane", [
        (1, "id", I64, OPT, "", 0), (2, "name", STR, OPT, "", 0),
        (3, "lines", MSG, REP, "XLine", 0),
        (4, "event_metadata", MSG, REP, "XPlane.EventMetadataEntry", 0),
        (5, "stat_metadata", MSG, REP, "XPlane.StatMetadataEntry", 0),
        (6, "stats", MSG, REP, "XStat", 0)])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry, [
            (1, "key", I64, OPT, "", 0), (2, "value", MSG, OPT, value, 0)])
        e.options.map_entry = True
    message(mt, "XLine", [
        (1, "id", I64, OPT, "", 0), (2, "name", STR, OPT, "", 0),
        (3, "timestamp_ns", I64, OPT, "", 0),
        (4, "events", MSG, REP, "XEvent", 0)])
    message(mt, "XEvent", [
        (1, "metadata_id", I64, OPT, "", 0),
        (2, "offset_ps", I64, OPT, "", 0),
        (3, "duration_ps", I64, OPT, "", 0),
        (4, "stats", MSG, REP, "XStat", 0)])
    message(mt, "XStat", [
        (1, "metadata_id", I64, OPT, "", 0),
        (2, "double_value", DBL, OPT, "", 1),
        (3, "uint64_value", U64, OPT, "", 1),
        (4, "int64_value", I64, OPT, "", 1),
        (5, "str_value", STR, OPT, "", 1),
        (6, "bytes_value", BYT, OPT, "", 1),
        (7, "ref_value", U64, OPT, "", 1)], oneof="value")
    message(mt, "XEventMetadata", [
        (1, "id", I64, OPT, "", 0), (2, "name", STR, OPT, "", 0),
        (4, "display_name", STR, OPT, "", 0),
        (5, "stats", MSG, REP, "XStat", 0)])
    message(mt, "XStatMetadata", [
        (1, "id", I64, OPT, "", 0), (2, "name", STR, OPT, "", 0)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench.xplane.XSpace"))


def parse(data: bytes):
    """An ``XSpace`` from the bytes of an ``.xplane.pb``."""
    space = _xspace_class()()
    space.ParseFromString(data)
    return space


def parse_text(text: str):
    """An ``XSpace`` from its text proto (the stored test fixtures), read
    by ``ProfileData``'s parser, many times faster than Python's."""
    from jax.profiler import ProfileData
    return parse(ProfileData.text_proto_to_serialized_xspace(text))


def load(trace_dir: str) -> "ProgramTrace":
    with open(xplane.find_xplane(trace_dir), "rb") as f:
        return reduce_xspace(parse(f.read()))


# -- reduction ---------------------------------------------------------------
def stat_values(plane, stats_) -> Dict[str, Any]:
    """``{stat name: value}``; a ``ref_value`` reads as the name of the
    stat metadata it refers to (how the profiler stores repeated strings)."""
    out = {}
    for s in stats_:
        meta = plane.stat_metadata.get(s.metadata_id)
        which = s.WhichOneof("value")
        if meta is None or which is None:
            continue
        v = getattr(s, which)
        if which == "ref_value":
            ref = plane.stat_metadata.get(v)
            v = ref.name if ref is not None else v
        out[meta.name] = v
    return out


def _events(line):
    """(event, start s, end s), computed as ``ProfileData`` and
    ``xplane.py`` compute them, so the same event gives the same floats."""
    t0 = line.timestamp_ns
    for e in line.events:
        a = (t0 + e.offset_ps / 1000.0) * 1e-9
        yield e, a, a + e.duration_ps / 1000.0 * 1e-9


def leaf_ops(ops: Sequence[Op]) -> List[Op]:
    """The ops that contain no other op (a ``while`` or ``conditional``
    spans the ops of its body: counting both would count that time
    twice)."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    parent = [False] * len(order)
    open_: List[int] = []
    for i, o in enumerate(order):
        while open_ and order[open_[-1]].end <= o.start:
            open_.pop()
        if open_ and o.end <= order[open_[-1]].end:
            parent[open_[-1]] = True
        open_.append(i)
    return [o for o, p in zip(order, parent) if not p]


def _ops(plane, line):
    """The ops of one ``XLA Ops`` line, each with its ``op_name`` from its
    event's stats, else from its metadata's."""
    meta = plane.event_metadata
    ids = {k for k, m in plane.stat_metadata.items()
           if m.name == OP_NAME_STAT}
    by_meta: Dict[int, str] = {}
    for e, a, b in _events(line):
        on = ""
        if e.stats:
            on = stat_values(plane, [s for s in e.stats
                                     if s.metadata_id in ids]).get(
                OP_NAME_STAT, "")
        if not on:
            on = by_meta.get(e.metadata_id)
            if on is None:
                on = by_meta[e.metadata_id] = stat_values(
                    plane, meta[e.metadata_id].stats).get(OP_NAME_STAT, "")
        yield Op(meta[e.metadata_id].name, a, b, str(on))


def reduce_xspace(space) -> ProgramTrace:
    spans: List[Span] = []
    devices: List[Device] = []
    for plane in space.planes:
        meta = plane.event_metadata
        if xplane._DEVICE.match(plane.name):
            ops: List[Op] = []
            mods = []
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    mods.extend((meta[e.metadata_id].name, a, b)
                                for e, a, b in _events(line))
                elif line.name == xplane.OPS_LINE:
                    ops.extend(_ops(plane, line))
            devices.append(Device(leaf_ops(ops), mods))
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for e, a, b in _events(line):
                    name = meta[e.metadata_id].name
                    if name.startswith(PREFIX):
                        spans.append(Span(name, (plane.name, li), a, b,
                                          stat_values(plane, e.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return ProgramTrace(spans, devices)


# -- readings from spans -----------------------------------------------------
def engine_thread(pt: ProgramTrace) -> Optional[Tuple[str, int]]:
    """The host line that holds ``ficabu.engine.step``."""
    for s in pt.spans:
        if s.name == STEP:
            return s.thread
    return None


def _within(s: Span, outer: Span) -> bool:
    return (s.thread == outer.thread and outer.start <= s.start
            and s.end <= outer.end and s is not outer)


def step_host_ms(pt: ProgramTrace) -> Optional[float]:
    steps = [s for s in pt.spans if s.name == STEP]
    if not steps:
        return None
    out = []
    for st in steps:
        busy = sum(c.end - c.start for c in pt.spans
                   if c.name in (ADMIT, PUBLISH) and _within(c, st))
        out.append((st.end - st.start - busy) * 1e3)
    return stats.median(out)


def admit_host_ms(pt: ProgramTrace) -> Optional[float]:
    admits = [s.end - s.start for s in pt.spans if s.name == ADMIT]
    return sum(admits) / len(admits) * 1e3 if admits else None


def scope_re(scope: str) -> "re.Pattern":
    """Matches an ``op_name`` that holds ``scope`` as a path component
    (``jit(sweep)/while/checkpoint/dot_general:``)."""
    return re.compile(r"(^|/)" + re.escape(scope) + r"(/|:|$)")


def scoped_share(dev: Device, program: str, scope: str
                 ) -> Optional[Tuple[float, float, int]]:
    """(scope seconds, program seconds, launches) of one device: the union
    of the leaf-op intervals in the named scope ``scope``, clipped to the
    launches of ``program``; the union of those launches; their count.
    None where the program has no launch."""
    launches = [(a, b) for n, a, b in dev.modules
                if xplane.program_name(n) == program]
    runs = xplane.union(launches)
    if not runs:
        return None
    starts = [a for a, _ in runs]
    pat = scope_re(scope)
    scoped = []
    for o in dev.ops:
        if not pat.search(o.op_name):
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < runs[i][1]:
            scoped.append((o.start, min(o.end, runs[i][1])))
    return (xplane.total(xplane.union(scoped)), xplane.total(runs),
            len(launches))


def scoped(pt: ProgramTrace, program: str, scope: str
           ) -> Optional[Tuple[float, float, int]]:
    """``scoped_share`` summed over the devices; None where the program has
    no launch on any."""
    got = [g for g in (scoped_share(d, program, scope) for d in pt.devices)
           if g is not None]
    if not got:
        return None
    return (sum(g[0] for g in got), sum(g[1] for g in got),
            sum(g[2] for g in got))


def sweep_checkpoint_pct(pt: ProgramTrace) -> Optional[float]:
    got = scoped(pt, SWEEP_PROGRAM, SCOPE)
    return None if got is None else 100.0 * got[0] / got[1]


# -- readings from counters --------------------------------------------------
def counter_delta(before: Dict[str, Any], after: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """``after - before`` for every number both dicts hold, nested dicts
    recursively; other values are left out."""
    out: Dict[str, Any] = {}
    for k, v in after.items():
        b = before.get(k)
        if isinstance(v, dict) and isinstance(b, dict):
            out[k] = counter_delta(b, v)
        elif (isinstance(v, (int, float)) and isinstance(b, (int, float))
              and not isinstance(v, bool)):
            out[k] = v - b
    return out


def publish_wait_ms(counters: Optional[Dict[str, Any]]) -> Optional[float]:
    """Seconds the engine thread blocked joining unfinished drains, per
    publication, over a window's counter delta."""
    eng = (counters or {}).get("engine") or {}
    if "publish_wait_s" not in eng or not eng.get("publications"):
        return None
    return eng["publish_wait_s"] / eng["publications"] * 1e3


def drain_host_ms(counters: Optional[Dict[str, Any]]) -> Optional[float]:
    """Worker seconds inside a drain less those blocked reading the
    sweep's outputs, per drain group, over a window's counter delta."""
    drn = (counters or {}).get("drain") or {}
    if "drain_s" not in drn or "sweep_wait_s" not in drn \
            or not drn.get("groups"):
        return None
    return (drn["drain_s"] - drn["sweep_wait_s"]) / drn["groups"] * 1e3


# -- idle gaps ---------------------------------------------------------------
def idle_gaps(tr: "xplane.Trace", pt: ProgramTrace, n: int = 10
              ) -> List[Tuple[str, float]]:
    """``xplane.Trace.idle_gaps`` with each name followed by
    ``/<innermost ficabu.* span of the engine thread covering the gap>``
    where one covers it."""
    thread = engine_thread(pt)
    eng = [s for s in pt.spans if s.thread == thread]
    out = []
    for best, a, b in tr.named_gaps(n):
        inner = [s for s in eng if s.start <= a and b <= s.end]
        if inner:
            best += "/" + min(inner, key=lambda s: s.end - s.start).name
        out.append((best, b - a))
    return out
