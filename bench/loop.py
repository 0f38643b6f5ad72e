"""The open-loop client: drives ``StreamEngine.step_once`` in a loop and
feeds it requests as they fall due by the wall clock.

Between two steps the client enqueues every generate request that is due,
submits at most one due forget request (``due_batch`` = the current step, so
each drain stays one forget set and one compiled sweep signature; a second
request due in the same gap waits for the next step, and its latency counts
the wait), steps the engine, and then reads the PREVIOUS step's tokens to
the host, so the device queue never runs empty.  Each read is timestamped;
a request's tokens are timed by the reads of the steps that produced them,
and every request is timed from its due time.

Requests due in the window are served to the end: after the window closes
the client keeps stepping, with no new arrivals, until every generate
request has its last token and every forget request is published, or until
``grace`` seconds have passed (what is left then has failed).  A ramp
(``finish=False``) stops when its schedule's time is up and leaves its
requests in flight, so that a window that follows starts on a pool at
steady occupancy.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np


class Window:
    """What one window recorded; all times in seconds from its start."""

    def __init__(self):
        self.t0 = 0.0                            # clock() at the start
        self.gen_due: Dict[int, float] = {}      # sid -> due time
        self.gen_prompt: Dict[int, int] = {}     # sid -> prompt index
        self.admit_step: Dict[int, int] = {}     # sid -> admission step
        self.read_time: Dict[int, float] = {}    # step -> host read time
        self.version_at: Dict[int, int] = {}     # step -> live version
        self.forget_due: List[float] = []        # per submitted request
        self.forget_domain: List[int] = []
        self.forget_version: List[int] = []      # version that holds it
        self.publish_time: Dict[int, float] = {}  # version -> time
        self.lateness: List[float] = []          # generator, per arrival
        self.occupied: List[int] = []            # pool slots, per step
        self.first_step = 0
        self.last_step = 0
        self.trace_steps = (None, None)           # steps dispatched traced
        self.trace_span = (None, None)


def serve(srv, sched: Dict[str, Any], prompts: np.ndarray, *,
          seconds: float, gen_len: int, clock: Callable[[], float],
          grace: float = 60.0, sid0: int = 0, finish: bool = True,
          on_publish: Optional[Callable[[int], None]] = None,
          trace: Optional[Dict[str, Any]] = None) -> Window:
    """Run one window against ``srv`` (a ``system.Served``).

    ``on_publish(version)`` (optional) is called after a step that
    published, with the new live version.  ``trace`` (optional) =
    ``{"start": s, "stop": s, "begin": fn, "end": fn, "span": fn(name) ->
    context manager}``: the profiler is started and stopped at those window
    times, and host phases are annotated."""
    w = Window()
    gen, fgt = sched["generate"], sched["forget"]
    t0 = w.t0 = clock()
    w.first_step = srv.step_index
    v_base = srv.version
    known = {s for s in srv.slots() if s is not None}
    pending_f: deque = deque()
    gi = fi = 0
    prev = None
    last_of = gen_len - 2        # the step offset of a request's last token
    n_submitted = 0
    tracing = False
    span = (trace or {}).get("span") or (lambda name: contextlib.nullcontext())

    def now():
        return clock() - t0

    def finished() -> bool:
        if gi < len(gen) or fi < len(fgt) or pending_f:
            return False
        if srv.version < v_base + n_submitted:
            return False
        return all(s in w.admit_step
                   and w.admit_step[s] + last_of in w.read_time
                   for s in w.gen_due)

    while True:
        t = now()
        if trace is not None:
            if not tracing and w.trace_span[0] is None and t >= trace["start"]:
                trace["begin"]()
                tracing = True
                w.trace_span = (now(), None)
                w.trace_steps = (srv.step_index, None)
            elif tracing and t >= trace["stop"]:
                # the span ends before the profiler stops: collecting and
                # writing the trace takes seconds that no step runs in
                w.trace_span = (w.trace_span[0], now())
                w.trace_steps = (w.trace_steps[0], srv.step_index)
                trace["end"]()
                tracing = False
        with span("bench.generator"):
            while gi < len(gen) and gen[gi][0] <= t:
                sid = sid0 + gi
                srv.enqueue(sid, prompts[gen[gi][1] % len(prompts)])
                w.gen_due[sid] = gen[gi][0]
                w.gen_prompt[sid] = gen[gi][1]
                w.lateness.append(t - gen[gi][0])
                gi += 1
            while fi < len(fgt) and fgt[fi][0] <= t:
                pending_f.append(fgt[fi])
                w.lateness.append(t - fgt[fi][0])
                fi += 1
            if pending_f:
                due, dom = pending_f.popleft()
                srv.submit_forget(dom)
                n_submitted += 1
                w.forget_due.append(due)
                w.forget_domain.append(dom)
                w.forget_version.append(v_base + n_submitted)
        if not srv.busy() and prev is not None:
            np.asarray(prev[1])          # nothing queued behind it
            w.read_time[prev[0]] = now()
            prev = None
        if finished() or t > seconds + grace or (not finish
                                                 and t >= seconds):
            break
        if not srv.busy():
            nxt = [a[0] for a in (gen[gi:gi + 1] + fgt[fi:fi + 1])]
            if nxt:
                time.sleep(max(0.0, min(nxt) - now()))
            continue
        s = srv.step_index
        v0 = srv.version
        with span("bench.step_once"):
            srv.step()
        v1 = srv.version
        if v1 != v0:
            tp = now()
            for v in range(v0 + 1, v1 + 1):
                w.publish_time[v] = tp
            if on_publish is not None:
                on_publish(v1)
        w.version_at[s] = v1
        n_occ = 0
        for sid in srv.slots():
            if sid is None:
                continue
            n_occ += 1
            if sid not in known:
                known.add(sid)
                w.admit_step[sid] = s
        w.occupied.append(n_occ)
        tok = srv.last_tokens
        if prev is not None:
            with span("bench.read"):
                np.asarray(prev[1])
            w.read_time[prev[0]] = now()
        prev = (s, tok)
    if tracing:
        w.trace_span = (w.trace_span[0], now())
        w.trace_steps = (w.trace_steps[0], srv.step_index)
        trace["end"]()
    if prev is not None:
        np.asarray(prev[1])
        w.read_time[prev[0]] = now()
    w.last_step = srv.step_index
    return w


def request_times(w: Window, gen_len: int) -> Dict[int, List[float]]:
    """Token arrival times per finished generate request: the first two
    tokens (prefill's and the admission step's decode) arrive with the
    admission step's read, each later token with its own step's read."""
    out = {}
    for sid in w.gen_due:
        a = w.admit_step.get(sid)
        if a is None:
            continue
        steps = [a] + [a + k for k in range(gen_len - 1)]
        if all(s in w.read_time for s in steps):
            out[sid] = [w.read_time[s] for s in steps]
    return out


def versions_of(w: Window, sid: int, prompt_len: int, gen_len: int
                ) -> np.ndarray:
    """The weight version that served each input position of a request:
    the prompt at its admission step, position ``P + k`` at step ``a + k``."""
    a = w.admit_step[sid]
    v = np.empty(prompt_len + gen_len - 1, np.int64)
    v[:prompt_len] = w.version_at[a]
    for k in range(gen_len - 1):
        v[prompt_len + k] = w.version_at[a + k]
    return v
