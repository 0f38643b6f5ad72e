"""The system under test, as the benchmark drives it.

Every call into the program (``repro``, under ``src/``) goes through this
file, so the rest of the harness — traffic, timing, the reference and the
comparison — depends on nothing of the program but what is named here:

  * ``build_server``     ``ForgetService`` (scanned sweep, the program's own
                         defaults for chunking, admission and publication)
                         under a ``StreamEngine`` sized by the cell;
  * ``Served``           the handful of engine and service attributes the
                         open-loop client reads between steps, and their
                         counters, read around the window.

What depends on the model's architecture (its ``LMConfig``, and the
benchmark's weight layout <-> the program's parameter tree) is the family's
(``bench/families/<family>.py``: ``lm_config``, ``program_tree``,
``neutral_tree``), handed in by the caller.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _program():
    """Import the program; a checkout without ``src/`` fails here."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.api import ServeSpec
    from repro.launch.serve import ForgetService, StreamEngine
    from repro.models.lm import LMConfig
    return ServeSpec, ForgetService, StreamEngine, LMConfig


class Served:
    """The engine and its forget service, with the reads the client makes."""

    def __init__(self, svc, engine, family):
        self.svc = svc
        self.engine = engine
        self.family = family
        self.drain_spans: List[List[float]] = []   # [start, end] per sweep

    # -- traffic -----------------------------------------------------------
    def enqueue(self, sid: int, prompt) -> None:
        self.engine.enqueue(sid, prompt)

    def submit_forget(self, domain: int) -> None:
        self.svc.submit(int(domain), due_batch=self.engine.step)

    def step(self) -> None:
        self.engine.step_once()

    # -- reads between steps (host bookkeeping, no device sync) ------------
    @property
    def step_index(self) -> int:
        return self.engine.step

    @property
    def admit_width(self) -> int:
        return self.engine.admit_chunk

    @property
    def version(self) -> int:
        return self.svc.params_version

    @property
    def last_tokens(self):
        """The device array of the newest decode step's tokens."""
        return self.engine.tok

    def slots(self) -> List[Optional[int]]:
        return list(self.engine.slot_seq)

    def busy(self) -> bool:
        e = self.engine
        return bool(e.pending or any(s is not None for s in e.slot_seq)
                    or e._pending_pubs or self.svc.scheduler.pending())

    def counters(self) -> Dict[str, Any]:
        """The engine's and the forget service's own counters
        (``StreamEngine.stats``, ``ForgetService.stats``)."""
        return {"engine": self.engine.stats(), "drain": self.svc.stats()}

    def results(self) -> Dict[int, Any]:
        return self.engine.results

    def drain_log(self) -> List[Dict[str, Any]]:
        """One entry per swept forget request: domain and halting layer."""
        return [{"domain": e["domain"], "stopped_at_l": e["stopped_at_l"]}
                for e in self.svc.log if "stopped_at_l" in e]

    def aborts(self) -> List[Dict[str, Any]]:
        """Drains that failed (guard or worker exception), in order."""
        return [dict(a) for a in self.svc.abort_log]

    def served_tree(self):
        """The tree the decode step reads (benchmark layout)."""
        return self.family.neutral_tree(self.engine.params)

    def warm_drain(self, domain: int) -> bool:
        """One drain through the engine's own sweep entry, on the live
        tree, left unpublished: it compiles (or loads) the sweep and the
        global Fisher, then drops the shadow state, so the live weights are
        untouched.  Returns whether the sweep ran."""
        _, ran, violation = self.svc.run_shadow_guarded(
            [int(domain)], self.engine.step)
        self.svc.discard_shadow()
        return violation is None and bool(ran)

    def time_drains(self, clock) -> None:
        """Record the host span of every sweep on the engine's worker."""
        inner = self.svc.run_shadow_guarded
        spans = self.drain_spans

        def timed(payloads, batch_idx):
            span = [clock(), None]
            spans.append(span)
            try:
                return inner(payloads, batch_idx)
            finally:
                span[1] = clock()

        self.svc.run_shadow_guarded = timed

    def close(self) -> None:
        """Join the sweep worker (every fired drain has published)."""
        self.engine.finish()


def build_server(fam, cfg: Dict[str, Any], weights: Dict[str, Any], tokens,
                 domains, seq_len: int, cell: Dict[str, Any], cache_dir: str,
                 precision: str = "fp32") -> Served:
    """The served deployment of one cell, for a model of family ``fam``
    with ``weights`` in that family's layout: a one-tenant ``ForgetService``
    (scanned sweep, step publication) under a ``StreamEngine`` of the cell's
    pool width and lengths.  Chunking, admission width, prefill block and
    publication lag are the program's own defaults."""
    ServeSpec, ForgetService, StreamEngine, LMConfig = _program()
    lcfg = fam.lm_config(cfg, LMConfig)
    serve = ServeSpec(cache_dir=cache_dir, sweep_mode="scanned",
                      publish="step", precision=precision,
                      max_batch=cell["pool_width"], tau=float(cell["tau"]),
                      max_forget_samples=cell["forget_set"])
    svc = ForgetService(lcfg, tokens, domains, seq_len, serve=serve)
    eng = StreamEngine(fam.program_tree(weights), lcfg,
                       gen_len=cell["output_len"],
                       prompt_len=cell["prompt_len"],
                       max_batch=serve.max_batch,
                       admit_chunk=serve.admit_chunk,
                       publish_lag=serve.publish_lag, service=svc)
    return Served(svc, eng, fam)
