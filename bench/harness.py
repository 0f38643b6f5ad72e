"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up (counted in ``setup_s``, from process start to the first timed
step): weights made on the device from the seed, the forget domains and
prompts, the served deployment, a warm-up that serves one admission's worth
of requests and — in a cell with forget traffic — runs one drain through
the engine's own sweep entry and drops it unpublished, so every program the
window runs is compiled (or loaded from the persistent cache) before it
starts; then a ramp of the cell's own generate traffic (``ramp_seconds``),
left in flight, so that the window starts on a pool at steady occupancy.
"""
from __future__ import annotations

import gc
import os
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import check
import loop
import spans
import stats
import system
import xplane
import weights as Wt
from registry import ROOT, Registry
from traffic import generator

WARM_SID = 1 << 40
RAMP_SID = 1 << 41
SAMPLE_REQUESTS = 8
TRACE_SECONDS = 4.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Compiles:
    """Counts backend compilations (a ``jax.monitoring`` listener)."""

    def __init__(self):
        self.n = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1


class RunView:
    """What a per-layer metric reader may read of a finished run."""

    def __init__(self, cfg, cell, peak, window, drain_spans, n_warm_drains,
                 trace, flops, program_trace, counters):
        self.cfg = cfg
        self.cell = cell
        self.peak = peak
        self.window = window
        self.drain_spans = drain_spans
        self.n_warm_drains = n_warm_drains
        self.trace = trace
        # the configuration's family: its operation and byte counts
        self.flops = flops
        # the program's own spans and the named scopes of its device ops
        # (``spans.ProgramTrace``), None untraced
        self.program_trace = program_trace
        # the engine's and the drain worker's counters over the window
        # (``spans.counter_delta`` of ``Served.counters``): every key their
        # ``stats()`` gives, nested dicts kept
        self.counters = counters

    def program(self, name: str):
        """(device seconds, launches) of a program in the trace, or None."""
        if self.trace is None:
            return None
        got = self.trace.program_seconds().get(name)
        return got if got and got[1] > 0 else None

    def scoped(self, program: str, scope: str):
        """(device seconds of the leaf ops in the named scope ``scope``
        inside ``program``'s launches, device seconds of those launches,
        launches), summed over the devices; None untraced or where the
        program has no launch."""
        if self.program_trace is None:
            return None
        return spans.scoped(self.program_trace, program, scope)


def peak_for(peaks: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """The chip's published peaks; a device kind not in the table is an
    error, never a default."""
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(peaks)})")
    return peaks[kind]


def _profile_options():
    """Device and host tracing without the Python tracer, which records
    every Python call and slows the client and the engine's host loop
    many times over while it is on."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")


def end_to_end(win: loop.Window, cell, setup_s: float) -> Dict[str, float]:
    G = cell["output_len"]
    times = loop.request_times(win, G)
    ttft = [times[s][0] - win.gen_due[s] for s in times]
    gaps: List[float] = []
    num = den = 0.0
    for t in times.values():
        gaps.extend(np.diff(t).tolist())
        num += t[-1] - t[0]
        den += len(t) - 1
    lat = [win.publish_time[v] - d
           for d, v in zip(win.forget_due, win.forget_version)
           if v in win.publish_time]
    out = {"setup_s": setup_s}
    if den:
        out["tpot_mean_ms"] = num / den * 1e3
    for name, vals, q, scale in (("ttft_p95_ms", ttft, 0.95, 1e3),
                                 ("itl_p999_ms", gaps, 0.999, 1e3),
                                 ("forget_p90_s", lat, 0.9, 1.0)):
        if not vals:
            continue
        try:
            out[name] = stats.percentile(vals, q) * scale
        except ValueError as e:     # too few samples: left out, not faked
            log(f"{name} not reported: {e}")
    return out


def _failures(win: loop.Window, cell) -> Dict[str, int]:
    done = loop.request_times(win, cell["output_len"])
    published = sum(1 for v in win.forget_version if v in win.publish_time)
    attempted = len(win.gen_due) + len(win.forget_due)
    return {"attempted": attempted,
            "failed": attempted - len(done) - published}


def _samples(win, srv, prompts, cell, seed, v_base: int
             ) -> List[Dict[str, np.ndarray]]:
    """Finished requests served, in part or whole, by versions 0 and 1
    (the seed's weights and the first published tree; counted from
    ``v_base``), drawn from the seed; the one with the most such positions
    always.  Versions are returned relative to ``v_base``."""
    G, P = cell["output_len"], cell["prompt_len"]
    done = []
    for s in sorted(loop.request_times(win, G)):
        v = loop.versions_of(win, s, P, G) - v_base
        if (v <= 1).any():
            done.append((s, v))
    if not done:
        return []
    done.sort(key=lambda sv: (-int((sv[1] <= 1).sum()), sv[0]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    rest = rng.permutation(len(done) - 1)[:SAMPLE_REQUESTS - 1] + 1
    res = srv.results()
    out = []
    for i in [0] + sorted(int(j) for j in rest):
        sid, v = done[i]
        served = np.asarray(res[sid]).astype(np.int32)
        out.append({"sid": sid,
                    "tokens": np.concatenate(
                        [prompts[win.gen_prompt[sid] % len(prompts)], served]),
                    "versions": v})
    return out


class Setup:
    """A cell after set-up: its files, the chip, the served deployment and
    its data, warmed up."""


def setup(workload: str, seed: int, seconds: float, *, root: str = ROOT,
          require_tpu: bool = True, control: bool = False,
          t_start: Optional[float] = None,
          hooks: Optional[Dict[str, Callable]] = None,
          overrides: Optional[Dict[str, Any]] = None) -> Setup:
    """Everything a window needs, counted in ``setup_s``.  ``control`` puts
    the lower-precision control in the program's place (the program's own
    int8 drains).
    ``hooks`` lets the tests break the served path (``hooks["server"](srv)``)
    and skip the chip (``require_tpu=False`` with ``hooks["peak"]``);
    ``overrides`` replaces keys of the cell (for calibration only)."""
    st = Setup()
    st.clock = clock = time.perf_counter
    t_start = clock() if t_start is None else t_start
    hooks = hooks or {}
    st.reg = reg = Registry(root)
    st.cell = reg.cell(workload)
    st.cfg = cfg = reg.config(st.cell["config"])
    st.family = fam = reg.family(cfg)
    st.cm = cm = dict(reg.mix(st.cell["traffic"]), **st.cell,
                      **(overrides or {}))
    import jax
    st.devs = devs = jax.devices()
    st.chips = reg.chips(workload)
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < st.chips:
        raise NoChip(f"cell {workload} needs {st.chips} chips, JAX sees "
                     f"{len(devs)}")
    st.kind = devs[0].device_kind
    st.peak = hooks["peak"] if "peak" in hooks else peak_for(reg.peaks(),
                                                             st.kind)
    cdir = cache_dir()
    os.makedirs(cdir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cdir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    st.compiles = Compiles()

    st.forget = float(cm.get("forget_rate", 0.0)) > 0
    st.sched = generator.schedule(cm, seed, seconds)
    w0 = Wt.make_weights(fam, cfg, seed)
    st.tokens, st.labels = Wt.make_domains(cfg, cm, seed)
    st.prompts = Wt.make_prompts(cfg, len(st.sched["generate"]) + 8,
                                 cm["prompt_len"], seed)
    st.srv = srv = system.build_server(
        fam, cfg, w0, st.tokens, st.labels, cm["forget_len"] + 1, cm, cdir,
        precision="int8" if control else "fp32")
    del w0
    srv.time_drains(clock)
    if "server" in hooks:
        hooks["server"](srv)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 4])
    for i in range(srv.admit_width):
        srv.enqueue(WARM_SID + i, st.prompts[-1 - i])
    while srv.busy():
        srv.step()
    np.asarray(srv.last_tokens)
    st.n_log0 = 0
    if st.forget:
        if not srv.warm_drain(int(rng.integers(cm["domains"]))):
            raise RuntimeError("the warm-up drain did not run")
        st.n_log0 = len(srv.drain_log())
        live = srv.served_tree()
        for b in check.changed_bits(live, live).values():
            np.asarray(b)
        del live
    ramp_s = float(cm.get("ramp_seconds", 0.0))
    if ramp_s > 0:
        ramp = generator.schedule(dict(cm, forget_rate=0.0), seed, ramp_s)
        loop.serve(srv, ramp, st.prompts, seconds=ramp_s,
                   gen_len=cm["output_len"], clock=clock, sid0=RAMP_SID,
                   finish=False)
    st.setup_s = clock() - t_start
    log(f"set-up {st.setup_s:.3f} s ({st.compiles.n} compiles), "
        f"{len(st.sched['generate'])} generate and "
        f"{len(st.sched['forget'])} forget requests due in {seconds} s")
    return st


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True, control: bool = False,
        t_start: Optional[float] = None,
        hooks: Optional[Dict[str, Callable]] = None,
        keep_trace: Optional[str] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result line's object.  ``control``
    also reads the fp8 reference as the served tokens; ``keep_trace``
    names a directory that receives a copy of the profiler trace;
    ``hooks["view"](view)`` receives the ``RunView`` of a traced run."""
    hooks = hooks or {}
    st = setup(workload, seed, seconds, root=root, require_tpu=require_tpu,
               control=control, t_start=t_start, hooks=hooks)
    import jax
    srv, cm, cfg, reg, clock = st.srv, st.cm, st.cfg, st.reg, st.clock
    devs, chips, kind, peak = st.devs, st.chips, st.kind, st.peak
    compiles, sched, prompts = st.compiles, st.sched, st.prompts
    forget, setup_s, fam = st.forget, st.setup_s, st.family
    tokens, labels, cell, n_log0 = st.tokens, st.labels, st.cell, st.n_log0
    del st

    # ---- the window --------------------------------------------------------
    tdir = tspec = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        # the last seconds of the window: stopping the profiler holds the
        # client for several times the traced span, and after the window
        # no request falls due that the pause could delay
        start = max(0.0, seconds - TRACE_SECONDS)
        tspec = {"start": start, "stop": seconds,
                 "begin": lambda: jax.profiler.start_trace(
                     tdir, profiler_options=_profile_options()),
                 "end": jax.profiler.stop_trace,
                 "span": jax.profiler.TraceAnnotation}
    # the first publication's edit, read from the tree the decode step then
    # holds against the one it held before (dispatched without a wait, and
    # copied to the host without one; the next publication drops the
    # device copy, so the harness holds no device memory the sweeps need)
    v_base = srv.version
    cap: Dict[str, Any] = {"base": srv.served_tree(), "bits": None}

    def on_publish(version: int) -> None:
        if cap["base"] is not None:
            if version == v_base + 1:
                cap["bits"] = check.changed_bits(srv.served_tree(),
                                                 cap["base"])
                for b in cap["bits"].values():
                    b.copy_to_host_async()
            cap["base"] = None
        elif cap["bits"] is not None:
            cap["bits"] = {k: np.asarray(b) for k, b in cap["bits"].items()}

    c0 = compiles.n
    before = srv.counters()
    win = loop.serve(srv, sched, prompts, seconds=seconds,
                     gen_len=cm["output_len"], clock=clock, trace=tspec,
                     on_publish=on_publish)
    counters = spans.counter_delta(before, srv.counters())
    cap["base"] = None
    n_compiles = compiles.n - c0
    srv.close()
    for a in srv.aborts():
        log(f"drain ABORTED at step {a.get('batch')}: {a.get('guard')} "
            f"{str(a.get('detail'))[:400]}")
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
              for d in devs[:chips]) if require_tpu else 0
    late = win.lateness or [0.0]
    log(f"window: {win.last_step - win.first_step} steps, {n_compiles} "
        f"compiles inside it, pool slots occupied median "
        f"{stats.median(win.occupied or [0])} of {cm['pool_width']}, "
        f"generator lateness p50 "
        f"{stats.median(late) * 1e3:.3f} ms max {max(late) * 1e3:.3f} ms")
    fails = _failures(win, cm)

    # ---- metrics -----------------------------------------------------------
    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(mem)}
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        t0 = clock()
        tr = xplane.load(tdir)
        span = win.trace_span
        progs = sorted(tr.program_seconds().items(), key=lambda kv: -kv[1][0])
        log("traced programs (device s, launches): " + ", ".join(
            f"{k} {s:.6f} {n}" for k, (s, n) in progs[:12]))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = span[1] - span[0]
        t1 = clock()
        pt = spans.load(tdir)
        t2 = clock()
        view = RunView(cfg, cm, peak, win,
                       [[a - win.t0, b - win.t0] for a, b in srv.drain_spans],
                       int(forget), tr, fam, pt, counters)
        if "view" in hooks:
            hooks["view"](view)
        for m in reg.per_layer(workload):
            val = reg.metric_reader(m["name"])(view)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
            else:
                log(f"MISSING per-layer metric {m['name']}: its reader "
                    f"found nothing in the trace (programs seen: "
                    f"{sorted(k for k, _ in progs)})")
        t3 = clock()
        breakdown = xplane.breakdown(tr)
        if breakdown is not None:
            breakdown["idle_gaps"] = [[k, v]
                                      for k, v in spans.idle_gaps(tr, pt)]
        log(f"trace read in {clock() - t0:.3f} s after the window: profile "
            f"{t1 - t0:.3f} s, program trace {t2 - t1:.3f} s "
            f"({len(pt.spans)} spans, "
            f"{sum(len(d.ops) for d in pt.devices)} leaf ops), readers "
            f"{t3 - t2:.3f} s, breakdown {clock() - t3:.3f} s")
        import shutil
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = end_to_end(win, cm, setup_s)
        log(f"samples: {len(loop.request_times(win, cm['output_len']))} "
            f"finished generate requests, {len(win.forget_due)} forget "
            f"requests; " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
        for m in reg.end_to_end(workload):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # ---- correctness -----------------------------------------------------
    samples = _samples(win, srv, prompts, cm, seed, v_base)
    prog_log = srv.drain_log()[n_log0:]
    drains = [{"domain": d,
               "prog_domain": prog_log[i]["domain"] if i < len(prog_log)
               else None,
               "prog_stop": prog_log[i]["stopped_at_l"] if i < len(prog_log)
               else None}
              for i, d in enumerate(win.forget_domain)]
    unpublished = len(win.forget_domain) - (srv.version - v_base)
    first_bits = (None if cap["bits"] is None else
                  {k: np.asarray(b) for k, b in cap["bits"].items()})
    del cap
    del srv
    gc.collect()
    t_ref = clock()
    got = check.compare(fam, cfg, cm, seed, tokens, labels, samples, drains,
                        first_bits, control=control)
    log(f"reference: {clock() - t_ref:.3f} s; {len(samples)} sampled "
        f"requests, {sum(int((s['versions'] == 0).sum()) for s in samples)} "
        f"positions served by the seed's weights, "
        f"{sum(int((s['versions'] == 1).sum()) for s in samples)} by the "
        f"first published tree")
    limits = cell["limits"]
    checks: Dict[str, Dict[str, float]] = {}
    value = dict(got)
    if control:
        value["decode_gap"] = got["control_gap"]
    for name, lim in limits.items():
        if name in value and value[name] is not None:
            checks[name] = {"value": value[name], "limit": lim}
    if forget:
        checks["unpublished_drains"] = {"value": unpublished, "limit": 0}
    correct = bool(samples) and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    if control:
        log(f"control readings: program decode_gap {got['decode_gap']} "
            f"fp8 reference decode_gap {got['control_gap']}")
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": correct, "attempted": fails["attempted"],
           "failed": fails["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
