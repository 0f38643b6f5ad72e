"""From a profiler trace to device busy time, per-program device time and
the idle gaps with what the host was doing in them.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's own
``ProfileData``.  Device planes are those named ``/device:TPU:<n>``; on each,
the ``XLA Ops`` line gives the intervals in which an operation ran (busy
time is their union) and the ``XLA Modules`` line one event per launch of a
compiled program, named after the jitted function (``jit__step(12)`` ->
``jit__step``).  Host spans are the harness's own annotations (``bench.*``)
on the host plane; both planes share the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit__step(12)`` -> ``jit__step``."""
    return _SUFFIX.sub("", event_name).strip()


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


class Device:
    """One device plane, reduced: busy intervals, program launches, ops."""

    def __init__(self, ops: List[Tuple[str, float, float]],
                 modules: List[Tuple[str, float, float]]):
        self.ops = ops
        self.modules = modules
        src = ops if ops else modules
        self.busy = union((a, b) for _, a, b in src)

    def program_seconds(self) -> Dict[str, Tuple[float, int]]:
        """Per program: (device seconds summed over launches, launches)."""
        out: Dict[str, List] = {}
        for name, a, b in self.modules:
            t = out.setdefault(program_name(name), [0.0, 0])
            t[0] += b - a
            t[1] += 1
        return {k: (v[0], v[1]) for k, v in out.items()}

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` operations that took most device time, each named
        ``<program>:<instruction>`` (``jit_sweep:%while.391``): the
        instruction's name without its HLO text, and the program whose
        launch holds it."""
        if not self.ops:
            src = [(program_name(m), a, b) for m, a, b in self.modules]
        else:
            mods = sorted(self.modules, key=lambda m: m[1])
            starts = [m[1] for m in mods]
            src = []
            for name, a, b in self.ops:
                i = bisect.bisect_right(starts, a) - 1
                prog = (program_name(mods[i][0])
                        if i >= 0 and a < mods[i][2] else "?")
                src.append((f"{prog}:{name.split(' = ', 1)[0]}", a, b))
        acc: Dict[str, float] = {}
        for name, a, b in src:
            acc[name] = acc.get(name, 0.0) + (b - a)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


class Trace:
    """A reduced trace: the device planes and the harness's host spans, all
    in seconds on the profiler's clock."""

    def __init__(self, devices: List[Device],
                 host_spans: List[Tuple[str, float, float]]):
        self.devices = devices
        self.host_spans = host_spans

    def bounds(self) -> Interval:
        """The traced window: the first to the last event of any plane."""
        pts = [t for d in self.devices for _, a, b in d.ops + d.modules
               for t in (a, b)] + [t for _, a, b in self.host_spans
                                   for t in (a, b)]
        return (min(pts), max(pts)) if pts else (0.0, 0.0)

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(total(d.busy) for d in self.devices) / len(self.devices)

    def program_seconds(self) -> Dict[str, Tuple[float, int]]:
        """Per program over all devices: (seconds, launches)."""
        out: Dict[str, List] = {}
        for d in self.devices:
            for k, (s, n) in d.program_seconds().items():
                t = out.setdefault(k, [0.0, 0])
                t[0] += s
                t[1] += n
        return {k: (v[0], v[1]) for k, v in out.items()}

    def named_gaps(self, n: int = 10) -> List[Tuple[str, float, float]]:
        """The ``n`` longest idle gaps of the first device, longest first
        (ties in time order), as (the host span that overlaps it most,
        ``host.other`` if none; start; end).  Only those ``n`` are named:
        a traced window holds thousands of gaps between ops."""
        if not self.devices:
            return []
        lo, hi = self.bounds()
        longest = sorted(gaps(self.devices[0].busy, lo, hi),
                         key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in longest:
            best, cover = "host.other", 0.0
            for name, s, e in self.host_spans:
                ov = min(b, e) - max(a, s)
                if ov > cover:
                    best, cover = name, ov
            out.append((best, a, b))
        return out

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest idle gaps of the first device, each named by
        the host span that overlaps it most (``host.other`` if none)."""
        return [(k, b - a) for k, a, b in self.named_gaps(n)]


def reduce_profile(pd, host_prefix: str = "bench.") -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    devices, spans = [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dst = ops if line.name == OPS_LINE else mods
                for e in line.events:
                    a = e.start_ns * 1e-9
                    dst.append((e.name, a, a + e.duration_ns * 1e-9))
            devices.append(Device(ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        a = e.start_ns * 1e-9
                        spans.append((e.name, a, a + e.duration_ns * 1e-9))
    return Trace(devices, spans)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))


def breakdown(tr: Trace, n: int = 10) -> Optional[Dict[str, list]]:
    if not tr.devices:
        return None
    return {"device_ops": [[k, v] for k, v in tr.devices[0].top_ops(n)],
            "idle_gaps": [[k, v] for k, v in tr.idle_gaps(n)]}
