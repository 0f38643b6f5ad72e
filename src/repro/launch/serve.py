"""Serving launcher with in-place unlearning between batches.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
        --requests 8 --gen-len 16 --forget-domains 1,2

``--smoke`` (the default) serves the registry's reduced config; ``--full``
serves the published widths and depth (gemma3-1b: 26 layers, d_model 1152,
vocab 262144, bf16) — sized for an accelerator, see ``chip_smoke.py``.

Serving loop: batched requests -> chunked prefill (``repro.models.lm.prefill``
consumes the prompt in blocks against the decode caches) -> iterative decode
with KV caches / recurrent states.  Forget requests can arrive at ANY point;
the server enqueues them, drains in-flight batches, applies FiCABU dampening
in place (no retraining, no weight reload — the paper's deployment story),
and continues serving with the edited weights.

Unlearning is driven exclusively through the ``repro.api.Unlearner``
facade, configured by one typed ``UnlearnSpec`` (DESIGN.md §9).  Forget
requests due at the same drain point are COALESCED: the drain unions them
into one group and runs a single back-end-first engine sweep
(``Unlearner.forget_group``) for the whole group — K queued deletions pay
one layer walk and one set of cached executables instead of K, while each
domain keeps its own halting/MAC accounting.  The facade keeps ONE warm
engine session across all drains: the first sweep pays compilation for each
unique layer shape, every later drain replays cached executables with zero
retraces (asserted by tests/test_engine.py and the ``--check`` CI gate).
The global Fisher importance I_D is likewise computed once per served model
(``Unlearner.ensure_fisher``), not per request.

``--forget-domains`` accepts burst syntax: ``1,2`` queues one request per
domain on consecutive batches (two drains); ``1,2;3,2`` queues bursts —
domains within a burst share a due batch and coalesce into one sweep.
``--coalesce`` folds a comma list into a single burst.  ``--check`` exits
non-zero if any drain ran more sweeps than coalesced groups or any drain
after the first recompiled.

JAX's persistent compilation cache is always on: it lives where
``JAX_COMPILATION_CACHE_DIR`` says when that is set (nothing overrides
it), else in ``--cache-dir`` (``ExecSpec.cache_dir``), else in the
checkout's ``.jax_cache``.  A COLD server start with a warm disk cache then
replays every compiled program — prefill, decode, and the engine's fused
steps — from disk.  With ``--check`` and a cache placed by the environment
or ``--cache-dir``, a warm-disk cold start that writes any new cache entry
(i.e. recompiled anything) fails the gate.

``--sweep-mode scanned`` (the default) serves every drain through the
whole-sweep megaprogram (``repro.engine.sweep``): the full back-end-first
sweep — vjp, Fisher, dampening, cotangent threading AND halt checkpoints —
is ONE compiled program per drain, halting decided on device with no host
sync mid-sweep.  With ``--check``, a drain that fell back to the layerwise
loop or launched more than one sweep program fails the gate.

``--fisher-refresh N`` arms the streamed global-Fisher refresh
(``RefreshSpec(every_drains=N)``, DESIGN.md §10): every N-th drain edits the
served weights AND then folds retain microbatches — evaluated at the
now-edited parameters — into an EMA of I_D through the structure-locked
``set_fisher`` path, so the dampening ratio I_Df/I_D keeps describing the
weights actually being served.  One compiled refresh program, hosted in the
same warm session as the fused steps; with ``--check`` the gate fails if any
refresh after the first compiled anything (a refresh-family cache
regression), if no refresh ran, or if the refreshed I_D is NOT closer than
the stale snapshot to a from-scratch recompute at the final weights (the
staleness oracle).

    PYTHONPATH=src python -m repro.launch.serve --smoke --requests 8 \
        --forget-domains 1,2 --fisher-refresh 1 --check

``--fleet fleet.json`` serves a MULTI-TENANT fleet (``repro.fleet``,
DESIGN.md §13): each declared tenant gets its own weights, domain data,
forget queue and tenant-scoped Fisher, while ONE ``DrainScheduler``
multiplexes drains across tenants (fair-share or deadline ordering from
the ``FleetSpec``) and ONE shared ``ProgramCache`` hosts every compiled
engine program — same-family tenants compile each program family exactly
once, however many of them the fleet serves.  With ``--check`` the fleet
run additionally gates: a drain whose (family, signature) was already
seen on ANY tenant must report zero compiles; a same-family tenant
replayed ALONE against a fresh program cache must (a) compile exactly the
programs the whole fleet compiled for that family and (b) end with
bit-identical weights and Fisher (tenant isolation).
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import time
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.api import (ServeSpec, UnlearnSpec, Unlearner,
                       compilation_cache_entries, enable_compilation_cache,
                       resolve_cache_dir)
from repro.api.facade import CACHE_ENV
from repro.data import LMDataConfig, make_lm_domains
from repro.fleet import Fleet, FleetSpec, TenantSpec
from repro.models import lm as LM
from repro.models.layers import Q_CHUNK
from repro.obs import telemetry as _t


def generate(params, cfg, prompts: jax.Array, gen_len: int,
             decode_jit, prefill_block: int = 8) -> np.ndarray:
    """prompts [B, P] -> greedy continuation [B, gen_len]."""
    B, Plen = prompts.shape
    S_max = Plen + gen_len
    cache = LM.init_cache(cfg, B, S_max)
    # chunked prefill: the prompt is consumed in blocks against the decode
    # caches (bit-exact vs the old token-by-token walk of the decode path,
    # see tests/test_models_smoke.py::test_chunked_prefill_bit_exact).
    logits, cache = LM.prefill(params, cfg, prompts, cache,
                               block=prefill_block)
    # tokens accumulate ON DEVICE and cross to the host ONCE at the end:
    # an np.asarray inside the loop would force a blocking device->host
    # sync every decode step, serializing the whole pipeline (bit-exact vs
    # the per-step-sync loop, see tests/test_stream.py).
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for j in range(gen_len):
        out.append(tok)
        logits, cache = decode_jit(params, cache, tok, jnp.int32(Plen + j))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return np.asarray(jnp.concatenate(out, axis=1))


def default_serve_spec(chunk_size: int = 4,
                       cache_dir: Optional[str] = None,
                       refresh_every: int = 0,
                       sweep_mode: str = "scanned",
                       precision: str = "fp32") -> UnlearnSpec:
    """Deprecated alias: build a ``ServeSpec`` and lower it.  The serving
    deployment's configuration now lives in the frozen, JSON-round-trippable
    ``repro.api.ServeSpec``; this shim keeps the historical helper working
    bit-identically."""
    return ServeSpec(chunk_size=chunk_size, cache_dir=cache_dir,
                     refresh_every=refresh_every, sweep_mode=sweep_mode,
                     precision=precision).to_unlearn_spec()


def _serve_spec_from_unlearn(spec: UnlearnSpec) -> ServeSpec:
    """Best-effort lift of a legacy engine-facing ``UnlearnSpec`` back to
    the serving-facing ``ServeSpec`` (for the deprecation shim's audit
    trail)."""
    return ServeSpec(
        chunk_size=spec.exec.chunk_size,
        refresh_every=(spec.refresh.every_drains
                       if spec.refresh is not None else 0),
        sweep_mode=spec.exec.sweep_mode,
        precision=spec.exec.precision,
        cache_dir=spec.exec.cache_dir,
        tau=spec.halt.tau)


class ForgetService:
    """Queue of forget requests + the warm ``Unlearner`` facade — now a
    thin single-tenant adapter over ``repro.fleet.Fleet``.

    ``submit`` enqueues; ``drain`` coalesces every request due at the drain
    point into ONE engine sweep over the unioned forget sets and returns the
    edited weights.  The drain mechanics (coalescing, pad-never-trim CHUNK
    alignment, drain-width equalization, streamed Fisher refresh, audit
    logs) live in ``repro.fleet.TenantRuntime``; this class routes the
    legacy single-tenant API through a one-tenant fleet bit-identically.

    Configure with a frozen ``repro.api.ServeSpec`` (``serve=``).  The old
    ``spec=UnlearnSpec`` signature (positional or keyword) still works but
    emits a ``DeprecationWarning``.
    """

    # deprecated: Fisher/engine chunk size now lives on ServeSpec.chunk_size
    CHUNK = 4

    def __init__(self, cfg, tokens, domains, seq_len: int,
                 serve: Optional[ServeSpec] = None, *,
                 spec: Optional[UnlearnSpec] = None, programs=None):
        if isinstance(serve, UnlearnSpec):
            # legacy 5th positional arg: ForgetService(..., unlearn_spec)
            warnings.warn(
                "passing an UnlearnSpec to ForgetService is deprecated; "
                "pass serve=ServeSpec(...) (repro.api.ServeSpec) instead",
                DeprecationWarning, stacklevel=2)
            spec, serve = serve, None
        elif spec is not None:
            warnings.warn(
                "ForgetService(spec=UnlearnSpec) is deprecated; pass "
                "serve=ServeSpec(...) (repro.api.ServeSpec) instead",
                DeprecationWarning, stacklevel=2)
        if serve is not None and not isinstance(serve, ServeSpec):
            raise ValueError(
                f"ForgetService serve= must be a repro.api.ServeSpec, "
                f"got {type(serve).__name__}")
        if serve is None:
            serve = (_serve_spec_from_unlearn(spec) if spec is not None
                     else ServeSpec(chunk_size=self.CHUNK))
        self.serve_spec = serve
        unlearn_spec = spec if spec is not None else serve.to_unlearn_spec()
        self.cfg = cfg
        self.tokens = tokens
        self.domains = domains
        self._fleet = Fleet(programs=programs)
        self._rt = self._fleet.add_tenant(
            "default", cfg, tokens, domains, seq_len, spec=unlearn_spec,
            tag="serve", coalesce=serve.coalesce,
            max_forget_samples=serve.max_forget_samples)

    # -- the legacy surface, delegated to the tenant runtime ---------------
    @property
    def queue(self) -> Deque[Dict]:
        """Read-only view of the pending forget queue (legacy shape — one
        entry per REQUEST, so admission-deferred folds are expanded)."""
        return deque({"domain": e["payload"], "due_batch": e["due_batch"]}
                     for e in self._fleet.scheduler.pending_entries(
                         self._rt.name))

    @property
    def scheduler(self):
        """The fleet's drain scheduler (one tenant here)."""
        return self._fleet.scheduler

    @property
    def adapter(self):
        return self._rt.adapter

    @property
    def spec(self) -> UnlearnSpec:
        return self._rt.spec

    @property
    def unlearner(self) -> Optional[Unlearner]:
        return self._rt.unlearner

    @property
    def log(self) -> List[Dict]:
        return self._rt.log

    @property
    def group_log(self) -> List[Dict]:
        return self._rt.group_log

    @property
    def refresh_log(self) -> List[Dict]:
        return self._rt.refresh_log

    @property
    def abort_log(self) -> List[Dict]:
        return self._rt.abort_log

    @property
    def sweeps(self) -> int:
        return self._rt.sweeps

    @property
    def groups(self) -> int:
        return self._rt.groups

    @property
    def stale_fisher(self):
        return self._rt.stale_fisher

    def stats(self) -> Dict:
        """The tenant runtime's counters (``TenantRuntime.stats``)."""
        return self._rt.stats()

    @property
    def retain_batches(self) -> List:
        return self._rt.retain_batches

    def submit(self, domain: int, due_batch: int) -> None:
        self._fleet.submit("default", domain, due_batch)

    def _warm(self, params) -> Unlearner:
        return self._rt._warm(params)

    def maybe_refresh(self, params, batch_idx: int) -> bool:
        """Streamed I_D refresh between drains (policy-scheduled)."""
        return self._rt.maybe_refresh(params, batch_idx)

    def staleness_report(self, params) -> Optional[Dict]:
        """The --check oracle: is the refreshed I_D closer than the stale
        one-shot snapshot to a from-scratch recompute at the CURRENT
        (edited) weights?"""
        return self._rt.staleness_report(params)

    def drain(self, params, batch_idx):
        """Coalesce all requests due at ``batch_idx`` into one sweep;
        returns (params, ran_any)."""
        self._rt.params = params
        entries = self._fleet.drain(batch_idx)
        return self._rt.params, any(e["ran"] for e in entries)

    # -- double-buffered stream-mode surface (DESIGN.md §15) ---------------
    @property
    def params(self):
        """The LIVE served tree (stream mode: the runtime's pointer IS the
        tree decode reads; it only moves via ``publish_staged``)."""
        return self._rt.params

    @property
    def params_version(self) -> int:
        return self._rt.params_version

    def install_params(self, params) -> None:
        """Install the live tree on the tenant runtime (stream mode)."""
        self._rt.params = params

    def run_shadow(self, payloads, batch_idx):
        """Drain body against the shadow tree — safe to call from the
        engine's worker thread; the live tree is untouched.  Returns
        ``(tree, ran)`` for the engine to stage/publish at its deadline."""
        return self._rt.run_due_shadow(list(payloads), batch_idx)

    def run_shadow_guarded(self, payloads, batch_idx):
        """``run_shadow`` + the guard violation captured on the SAME
        worker thread (reading ``last_violation`` at the publication
        deadline would race with a LATER sweep overwriting it on the
        serialized worker).  Returns ``(tree, ran, violation)``.
        Delegates through ``run_shadow`` so a stubbed shadow runner
        (tests, bench warmup) stays on the call path."""
        tree, ran = self.run_shadow(payloads, batch_idx)
        return tree, ran, self._rt.last_violation

    def abort_group(self, group, violation, step, tree=None) -> str:
        """Route a failed shadow sweep through the fleet's abort path
        (retry/backoff via the scheduler, then the dead-letter queue);
        the live tree keeps serving.  Returns the action taken."""
        return self._fleet._abort(group, self._rt, violation, step,
                                  "step", tree=tree)

    def book_skipped(self, payloads, batch) -> None:
        """Account a clean no-op drain (no forget samples for the due
        payloads): the requests are served, just with nothing to edit."""
        self._rt.book_applied(list(payloads), batch=batch)

    def stage(self, tree, *, payloads=None, batch=None) -> None:
        self._rt.stage(tree, payloads=payloads, batch=batch)

    def publish_staged(self, step=None) -> bool:
        """Atomic between-steps pointer swap of the staged tree."""
        return self._rt.publish_staged(step=step)

    def discard_shadow(self) -> None:
        """Drop unpublished shadow state (bench warmup hygiene)."""
        self._rt.discard_shadow()


# event kinds emitted on the ENGINE thread (deterministic order); sweep
# worker threads emit their own events at scheduler-dependent points
ENGINE_EVENT_KINDS = frozenset({"batch.admit", "batch.evict", "drain.fire",
                                "drain.abort", "params.publish"})


def engine_fingerprint(events) -> str:
    """Determinism fingerprint of the engine-side event stream.

    Keeps only ``ENGINE_EVENT_KINDS`` and drops the global ``seq``
    counter: seq numbers are allocated process-wide across threads, so a
    sweep worker finishing a GIL slice earlier or later shifts the seq
    values on engine events even though the engine-side ORDER (what the
    fingerprint must pin) is fully deterministic.
    """
    evs = [{k: v for k, v in e.items() if k != "seq"}
           for e in events if e.get("kind") in ENGINE_EVENT_KINDS]
    return _t.fingerprint(evs)


def admission_prefill_block(prompt_len: int) -> int:
    """Tokens per prefill launch for an admission of prompts ``prompt_len``
    tokens long: the whole prompt, one launch, when it is at most
    ``layers.Q_CHUNK`` — the model's bound on materialised score rows (a
    wide block holds a [width, H, block, S_max] f32 score block) — else
    the largest divisor of the prompt not above it, so every launch of an
    admission has one signature.  Where an attention cache can wrap or a
    block is recurrent, ``LM.prefill`` scans decode steps over the block
    instead; the block then only sets the launch count, and the same rule
    holds."""
    P = int(prompt_len)
    return max(d for d in range(1, min(P, Q_CHUNK) + 1) if P % d == 0)


class StreamEngine:
    """Continuous-batching decode engine with zero-downtime drains.

    A fixed pool of ``max_batch`` decode slots steps in lockstep through
    ONE jitted decode program (per-row positions, see
    ``models.layers.attention_decode``).  Per engine step the loop:

      1. PUBLISHES any shadow-drain result whose step deadline arrived —
         an atomic pointer swap BETWEEN decode steps, so a step can never
         observe a half-edited tree;
      2. fires newly due drains: the scheduler group is popped on the
         ENGINE thread (deterministic order) and the sweep runs on a
         single worker thread against the tenant's SHADOW tree
         (``ForgetService.run_shadow``) — serving never stalls for it;
      3. admits pending sequences into free slots via a fixed-width
         prefill (``models.lm.prefill``; one launch for a prompt of up to
         ``layers.Q_CHUNK`` tokens, see ``admission_prefill_block``)
         scattered into the pool caches (``models.lm.scatter_cache_rows``);
      4. evicts finished sequences (host-side length bookkeeping — no
         device sync) and starts an async device->host copy of their
         output row;
      5. dispatches the decode step WITHOUT syncing — JAX's in-flight
         queue provides natural back-pressure.

    Every engine-side transition emits a deterministic telemetry event
    (``batch.admit`` / ``batch.evict`` / ``drain.fire`` /
    ``params.publish``); worker-thread events interleave freely and are
    excluded from determinism fingerprints.  Publication happens at the
    deterministic deadline ``fire_step + publish_lag`` regardless of how
    fast the worker finishes, so two runs of the same scenario publish at
    identical steps with identical content (drain k+1 chains off drain
    k's output via the runtime's shadow chain).

    Each phase also opens a profiler span (``telemetry.span``):
    ``engine.step`` around the step, with ``engine.publish`` /
    ``engine.publish_wait`` / ``engine.fire`` / ``engine.admit`` /
    ``engine.evict`` / ``engine.decode`` inside it, and ``drain`` around
    each sweep on the worker thread, linked to its ``engine.fire`` and
    ``engine.publish_wait`` by ``fire_step`` and ``group`` (the group's
    position among that step's due groups); ``engine.admit`` carries the
    admission's prefill ``launches``.  ``stats()`` returns the engine's
    plain counters.
    """

    def __init__(self, params, cfg, *, gen_len: int, prompt_len: int,
                 max_batch: int = 8, admit_chunk: int = 4,
                 publish_lag: int = 16,
                 service: Optional[ForgetService] = None):
        if gen_len < 1 or prompt_len < 1:
            raise ValueError(f"StreamEngine needs gen_len/prompt_len >= 1, "
                             f"got {gen_len}/{prompt_len}")
        self.cfg = cfg
        self.params = params
        self.G = int(gen_len)
        self.P = int(prompt_len)
        self.B = int(max_batch)
        self.admit_chunk = min(int(admit_chunk), self.B)
        self.prefill_block = admission_prefill_block(self.P)
        self.publish_lag = int(publish_lag)
        self.svc = service
        if service is not None:
            service.install_params(params)
        self.S_max = self.P + self.G
        B, G = self.B, self.G
        self.cache = LM.init_cache(cfg, B, self.S_max)
        self.tok = jnp.zeros((B, 1), dtype=jnp.int32)
        self.pos = jnp.zeros((B,), dtype=jnp.int32)
        # gidx starts at G so an unoccupied slot's writes DROP out of the
        # output buffer (mode="drop" scatter) instead of clobbering it
        self.gidx = jnp.full((B,), G, dtype=jnp.int32)
        self.outbuf = jnp.zeros((B, G), dtype=jnp.int32)
        # host-side slot bookkeeping — never syncs the device
        self.slot_seq: List[Optional[int]] = [None] * B
        self.slot_written = [0] * B
        self.pending: Deque = deque()
        self.results: Dict[int, object] = {}
        self.step = 0
        self.publications = 0
        self.aborts = 0
        self.admissions = 0
        self.admitted_rows = 0
        self.padded_rows = 0
        self.prefill_launches = 0
        # publication deadlines that found the drain unfinished, and the
        # seconds the engine thread then spent blocked joining it
        self.publish_waits = 0
        self.publish_wait_s = 0.0
        self.step_wall: List[float] = []   # per-step loop seconds
        # [deadline_step, future, scheduler group, fire step, position] —
        # the group rides along so a failed sweep can be requeued/dead-
        # lettered at the deadline; fire step and position name it in spans
        self._pending_pubs: List[List] = []
        self._executor = None

        def _step(params, cache, tok, pos, gidx, outbuf):
            logits, cache = LM.decode_step(params, cfg, tok, cache, pos)
            ntok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            outbuf = outbuf.at[jnp.arange(B), gidx].set(ntok[:, 0],
                                                        mode="drop")
            return cache, ntok, pos + 1, gidx + 1, outbuf

        self._step_fn = jax.jit(_step)

        P = self.P

        def _admit(cache, sub_cache, tok, pos, gidx, outbuf, rows, first):
            cache = LM.scatter_cache_rows(cache, sub_cache, rows)
            tok = tok.at[rows].set(first, mode="drop")
            pos = pos.at[rows].set(P, mode="drop")
            # token 0 is the prefill argmax, already written at index 0
            gidx = gidx.at[rows].set(1, mode="drop")
            outbuf = outbuf.at[rows].set(0, mode="drop")
            outbuf = outbuf.at[rows, 0].set(first[:, 0], mode="drop")
            return cache, tok, pos, gidx, outbuf

        self._admit_fn = jax.jit(_admit)

    # -- traffic -----------------------------------------------------------
    def enqueue(self, seq_id: int, prompt) -> None:
        """Queue one sequence (prompt [P] tokens) for admission."""
        prompt = np.asarray(prompt)
        if prompt.shape != (self.P,):
            raise ValueError(f"StreamEngine prompts are fixed-length "
                            f"[{self.P}], got shape {prompt.shape}")
        if seq_id in self.results or seq_id in [s for s in self.slot_seq
                                                if s is not None]:
            raise ValueError(f"duplicate seq_id {seq_id}")
        self.pending.append((int(seq_id), prompt))

    def _admit_due(self) -> None:
        free = [i for i in range(self.B) if self.slot_seq[i] is None]
        while self.pending and free:
            take = min(len(free), len(self.pending), self.admit_chunk)
            chunk = [self.pending.popleft() for _ in range(take)]
            rows, free = free[:take], free[take:]
            width = self.admit_chunk
            seqs = [sid for sid, _ in chunk]
            launches = self.P // self.prefill_block
            with _t.span("engine.admit", seqs=seqs, width=width,
                         padded=width - take, launches=launches):
                # fixed-width sub-batch: ONE prefill/admit program
                # signature.  Padding rows repeat the last prompt and
                # scatter to row index B — out of bounds, dropped by the
                # mode="drop" scatters.
                prompts = np.stack([p for _, p in chunk]
                                   + [chunk[-1][1]] * (width - take))
                rows_arr = jnp.asarray(rows + [self.B] * (width - take),
                                       dtype=jnp.int32)
                sub_cache = LM.init_cache(self.cfg, width, self.S_max)
                logits, sub_cache = LM.prefill(self.params, self.cfg,
                                               jnp.asarray(prompts),
                                               sub_cache,
                                               block=self.prefill_block)
                first = jnp.argmax(logits[:, -1:],
                                   axis=-1).astype(jnp.int32)
                (self.cache, self.tok, self.pos, self.gidx,
                 self.outbuf) = self._admit_fn(
                    self.cache, sub_cache, self.tok, self.pos, self.gidx,
                    self.outbuf, rows_arr, first)
            for r, sid in zip(rows, seqs):
                self.slot_seq[r] = sid
                self.slot_written[r] = 1
            self.admissions += 1
            self.admitted_rows += take
            self.padded_rows += width - take
            self.prefill_launches += launches
            _t.emit("batch.admit", step=self.step, rows=rows,
                    seqs=seqs, width=width, padded=width - take)

    def _evict_done(self) -> None:
        for r in range(self.B):
            if self.slot_seq[r] is not None \
                    and self.slot_written[r] >= self.G:
                sid = self.slot_seq[r]
                row = self.outbuf[r]          # device gather, lazy
                # overlap the device->host copy with decode when the array
                # type supports it (a feature probe, not error handling)
                copy_async = getattr(row, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
                self.results[sid] = row
                _t.emit("batch.evict", step=self.step, row=r, seq=sid)
                self.slot_seq[r] = None
                self.slot_written[r] = 0

    # -- drains ------------------------------------------------------------
    def _fire_drains(self, step) -> None:
        svc = self.svc
        if svc is None:
            return
        nd = svc.scheduler.next_due()
        if nd is None or nd > step:
            return
        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1)   # serializes sweeps: drain k+1 after k
        for pos, g in enumerate(svc.scheduler.due_groups(step)):
            payloads = list(g.payloads)
            with _t.span("engine.fire", group=pos, payloads=payloads):
                fut = self._executor.submit(self._drain, payloads, step,
                                            pos)
            self._pending_pubs.append([step + self.publish_lag, fut, g,
                                       step, pos])
            _t.emit("drain.fire", step=step, n_requests=len(g.payloads),
                    payloads=payloads,
                    publish_at=step + self.publish_lag)

    def _drain(self, payloads, step, group):
        """One shadow sweep, on the worker thread."""
        with _t.span("drain", group=group, fire_step=step,
                     payloads=payloads):
            return self.svc.run_shadow_guarded(payloads, step)

    def _publish_due(self, step) -> None:
        if not self._pending_pubs:
            return
        due = [p for p in self._pending_pubs if p[0] <= step]
        if not due:
            return
        self._pending_pubs = [p for p in self._pending_pubs if p[0] > step]
        with _t.span("engine.publish"):
            self._publish(due)

    def _publish(self, due) -> None:
        svc = self.svc
        published = False
        for _, fut, g, fire_step, pos in due:
            # joining at the DEADLINE keeps the publication step (and the
            # published content, via the shadow chain) deterministic no
            # matter how thread timing interleaved the sweep itself
            tree = None
            violation = None
            if not fut.done():
                t0 = _t.monotonic()
                with _t.span("engine.publish_wait", group=pos,
                             fire_step=fire_step):
                    concurrent.futures.wait([fut])
                self.publish_waits += 1
                self.publish_wait_s += _t.monotonic() - t0
            try:
                tree, ran, violation = fut.result()
            except Exception as e:   # worker died: nothing staged, abort
                ran = False
                violation = {"guard": "exception", "detail": repr(e),
                             "applied_idx": [], "handled_idx": [],
                             "requeue_idx": list(range(len(g.payloads)))}
            if violation is not None:
                # the live tree keeps serving; the failed group goes back
                # through the scheduler (retry budget) or dead-letters
                self.aborts += 1
                svc.abort_group(g, violation, self.step, tree=tree)
                continue
            if ran:
                svc.stage(tree, payloads=list(g.payloads), batch=self.step)
                if svc.publish_staged(step=self.step):
                    self.publications += 1
                    published = True
            else:
                svc.book_skipped(list(g.payloads), batch=self.step)
        if published:
            self.params = svc.params

    # -- the loop ----------------------------------------------------------
    def step_once(self) -> None:
        t0 = _t.monotonic()
        with _t.span("engine.step", step_num=self.step):
            self._publish_due(self.step)
            self._fire_drains(self.step)
            self._admit_due()
            with _t.span("engine.evict"):
                self._evict_done()
            if any(s is not None for s in self.slot_seq):
                with _t.span("engine.decode"):
                    (self.cache, self.tok, self.pos, self.gidx,
                     self.outbuf) = self._step_fn(
                        self.params, self.cache, self.tok, self.pos,
                        self.gidx, self.outbuf)
                for r in range(self.B):
                    if self.slot_seq[r] is not None:
                        self.slot_written[r] += 1
                with _t.span("engine.evict"):
                    self._evict_done()
        self.step += 1
        self.step_wall.append(_t.monotonic() - t0)

    def stats(self) -> Dict[str, float]:
        """The engine's counters: steps, admissions and their rows (padding
        rows apart) and prefill launches, publications, aborts, and the
        publication deadlines that blocked on an unfinished drain with the
        seconds they blocked."""
        return {"steps": self.step, "admissions": self.admissions,
                "admitted_rows": self.admitted_rows,
                "padded_rows": self.padded_rows,
                "prefill_launches": self.prefill_launches,
                "publications": self.publications, "aborts": self.aborts,
                "publish_waits": self.publish_waits,
                "publish_wait_s": self.publish_wait_s}

    def run(self) -> Dict[int, np.ndarray]:
        """Serve until every enqueued sequence completed, then flush any
        drains still queued/unpublished and materialize the outputs."""
        while self.pending or any(s is not None for s in self.slot_seq):
            self.step_once()
        return self.finish()

    def finish(self) -> Dict[int, np.ndarray]:
        if self.svc is not None:
            # a forget request must never be silently dropped at shutdown —
            # and an abort at the publish deadline can REQUEUE work, so the
            # flush must alternate fire/publish until both the queue and
            # the in-flight publications are empty (termination: the retry
            # budget bounds requeues before the dead-letter queue takes
            # the group)
            while self.svc.scheduler.pending() or self._pending_pubs:
                while self.svc.scheduler.pending():
                    self._fire_drains(float("inf"))
                self._publish_due(float("inf"))
            if self._executor is not None:
                self._executor.shutdown(wait=True)
                self._executor = None
        return {sid: np.asarray(row)
                for sid, row in sorted(self.results.items())}

    def decode_cache_size(self) -> int:
        """Compiled-signature count of the decode step program — the
        zero-recompile-across-publications gate reads this."""
        return self._step_fn._cache_size()


def _build_lm_tenant(tspec: TenantSpec, args) -> Dict:
    """Model + synthetic domain data for one tenant, deterministic in the
    tenant's seed (the --check isolation replay rebuilds from this)."""
    arch = configs.get(tspec.arch)
    if arch.kind != "lm":
        raise ValueError(
            f"serve.py --fleet drives LM decode loops; tenant "
            f"{tspec.name!r} declares arch {tspec.arch!r}, a "
            f"{arch.kind!r} architecture — pick LM entries from "
            f"repro.configs")
    cfg = arch.full if args.full else arch.smoke
    params = LM.init_lm(jax.random.PRNGKey(tspec.seed), cfg)
    dcfg = LMDataConfig(vocab=cfg.vocab, n_domains=4,
                        seq_len=args.prompt_len + args.gen_len,
                        n_per_domain=16, seed=tspec.seed)
    tokens, domains = make_lm_domains(dcfg)
    return {"cfg": cfg, "tokens": tokens, "domains": domains,
            "seq_len": dcfg.seq_len, "params": params}


def _trees_bitwise_equal(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb or len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape \
                or not np.array_equal(x, y):
            return False
    return True


def _family_program_count(fleet: Fleet, adapter_name: str) -> int:
    """Compiled-program count attributable to one adapter family in the
    fleet's shared cache (every cached program compiled exactly once)."""
    return sum(n for ns, n in fleet.family_program_counts().items()
               if ns[0] == adapter_name)


def _solo_replay(fleet: Fleet, fspec: FleetSpec, name: str, args):
    """Replay ONE tenant's drains alone against a fresh program cache.

    Rebuilds the tenant's weights/data from its spec (deterministic in the
    seed) and re-runs exactly the drain groups the fleet ran for it, in
    order.  Generation is skipped — it never mutates params — so the solo
    endpoint must be bit-identical to the tenant's in-fleet state, and the
    fresh cache's compile count for the family is the N=1 baseline the
    shared cache is gated against."""
    tspec = fspec.tenant(name)
    built = _build_lm_tenant(tspec, args)
    solo = Fleet(scheduling=fspec.scheduling,
                 max_groups_per_drain=fspec.max_groups_per_drain)
    rt = solo.add_tenant(tspec, built["cfg"], built["tokens"],
                         built["domains"], built["seq_len"],
                         params=built["params"],
                         spec=fspec.tenant_unlearn_spec(name),
                         coalesce=fspec.serve.coalesce,
                         max_forget_samples=fspec.serve.max_forget_samples)
    for e in fleet.drain_log:
        if e["tenant"] == name:
            rt.params, _ = rt.run_due(rt.params, e["payloads"], e["batch"])
    return solo, rt


def _shared_family_tenant(fleet: Fleet, fspec: FleetSpec) -> Optional[str]:
    """A tenant that BENEFITED from cross-tenant sharing: drained at least
    once, and some other tenant has the same arch + identical effective
    UnlearnSpec (so their program families coincide exactly)."""
    by_family: Dict = {}
    for name, rt in fleet.tenants.items():
        key = (rt.arch, json.dumps(fspec.tenant_unlearn_spec(name)
                                   .to_dict(), sort_keys=True))
        by_family.setdefault(key, []).append(name)
    for names in by_family.values():
        drained = [n for n in names if fleet.tenants[n].groups > 0]
        if len(names) >= 2 and drained:
            return drained[-1]  # the latest-drained: warmed by its siblings
    return None


def _open_cache(cache_dir: Optional[str]) -> Dict:
    """Enable the persistent compilation cache before the first compile:
    the environment's ``JAX_COMPILATION_CACHE_DIR``, else ``cache_dir``,
    else the checkout's ``.jax_cache``.  The cold-start gate is armed only
    where the location was chosen on purpose — the checkout's default is
    shared by every command run from it."""
    path = resolve_cache_dir(cache_dir)
    return {"dir": path, "entries_before": enable_compilation_cache(path),
            "gated": bool(cache_dir or os.environ.get(CACHE_ENV))}


def _close_cache(cache_info: Dict) -> Dict:
    return dict(cache_info, entries_new=(
        compilation_cache_entries(cache_info["dir"])
        - cache_info["entries_before"]))


def _cold_start_problem(cache_info: Dict) -> Optional[str]:
    """The cold-start gate: a process start against a WARM disk cache must
    replay every program (prefill, decode, fused steps) from disk — any new
    cache entry is a recompile the persistence layer missed."""
    if cache_info["gated"] and cache_info["entries_before"] > 0 \
            and cache_info["entries_new"] > 0:
        return (f"cold start with a warm compilation cache "
                f"({cache_info['entries_before']} entries) still compiled "
                f"{cache_info['entries_new']} new program(s)")
    return None


def _main_fleet(args) -> dict:
    fspec = FleetSpec.from_file(args.fleet)
    cache_info = _open_cache(fspec.serve.cache_dir or args.cache_dir)

    fleet = Fleet.from_spec(fspec, lambda t: _build_lm_tenant(t, args))

    # decode programs are shared per family too: one decode_jit per arch
    decode_jits: Dict[str, object] = {}
    for rt in fleet.tenants.values():
        if rt.arch not in decode_jits:
            cfg = rt.cfg
            decode_jits[rt.arch] = jax.jit(
                lambda p, c, t, pos, _cfg=cfg:
                LM.decode_step(p, _cfg, t, c, pos))

    # the burst schedule applies to EVERY tenant — simultaneous deadlines
    # are exactly the contention the scheduler policy has to arbitrate
    if args.unlearn_after >= 0:
        for i, burst in enumerate(_parse_bursts(args)):
            for name in fleet.tenants:
                for d in burst:
                    fleet.submit(name, d, due_batch=args.unlearn_after + i)

    served: Dict[str, List[dict]] = {name: [] for name in fleet.tenants}
    tenant_batches = {
        name: [rt.tokens[i:i + args.requests, :args.prompt_len]
               for i in range(0, len(rt.tokens) - args.requests,
                              args.requests)][:3]
        for name, rt in fleet.tenants.items()}
    n_batches = min(len(b) for b in tenant_batches.values())
    for bi in range(n_batches):
        for name, rt in fleet.tenants.items():
            t0 = time.time()
            gen = generate(rt.params, rt.cfg,
                           jnp.asarray(tenant_batches[name][bi]),
                           args.gen_len, decode_jits[rt.arch],
                           prefill_block=args.prefill_block)
            entry = {"batch": bi,
                     "latency_s": round(time.time() - t0, 3),
                     "tokens": int(gen.size)}
            served[name].append(entry)
            _t.emit("request.generate", tenant=name, **entry)
        fleet.drain(bi + 1)
    # flush requests still queued past the last served batch — a forget
    # request must never be silently dropped at shutdown (the per-drain
    # group budget may need several flush rounds)
    while fleet.scheduler.pending():
        fleet.drain(float("inf"))

    cache_info = _close_cache(cache_info)
    result = {
        "fleet": fspec.to_dict(),
        "served": served,
        "tenants": {
            name: {"unlearn_requests": rt.log, "group_log": rt.group_log,
                   "coalesced_groups": rt.groups, "sweeps": rt.sweeps,
                   "refresh_log": rt.refresh_log,
                   "engine_stats": (dict(rt.unlearner.stats)
                                    if rt.unlearner is not None else {})}
            for name, rt in fleet.tenants.items()},
        "drain_log": [{k: e.get(k) for k in ("tenant", "batch", "payloads",
                                             "ran", "aborted", "missed")}
                      for e in fleet.drain_log],
        "fleet_stats": fleet.stats(),
        "compilation_cache": cache_info,
    }
    _t.log("serve", f"fleet done: {json.dumps(result)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)

    if args.check:
        problems = []
        # guarded-drain gate: a fault-free fleet serve must never abort a
        # drain, dead-letter a request, or break the request accounting
        for name, rt in fleet.tenants.items():
            if rt.aborts:
                problems.append(
                    f"tenant {name!r}: {rt.aborts} drain abort(s) "
                    f"(last: {rt.abort_log[-1].get('guard')!r}) in a "
                    "fault-free serve")
        if fleet.scheduler.dead():
            problems.append(
                f"{fleet.scheduler.dead()} forget request(s) dead-lettered "
                "in a fault-free serve")
        for name, acct in fleet.accounting().items():
            if not acct["ok"]:
                problems.append(
                    f"tenant {name!r}: request accounting broken — "
                    f"{acct['submitted']} submitted != {acct['applied']} "
                    f"applied + {acct['pending']} pending + "
                    f"{acct['staged']} staged + {acct['dead']} dead")
        # per-tenant coalescing gate: ONE engine sweep per drain point
        if fspec.serve.coalesce:
            for name, rt in fleet.tenants.items():
                sweeps_by_batch: Dict = {}
                for g in rt.group_log:
                    sweeps_by_batch[g["batch"]] = \
                        sweeps_by_batch.get(g["batch"], 0) + g["sweeps"]
                for b, n in sorted(sweeps_by_batch.items()):
                    if n > 1:
                        problems.append(
                            f"tenant {name!r}: drain at batch {b} ran {n} "
                            "engine sweeps — due requests were not "
                            "coalesced into one group")
        # cross-tenant recompile gate: once ANY tenant has drained a
        # (family, precision, sweep-mode, signature), every later drain of
        # it — on ANY tenant — must replay the shared cache, zero compiles.
        # This is the sharing contract made observable: tenant B's first
        # drain after same-family tenant A is already warm.
        seen_sigs = set()
        for e in fleet.drain_log:
            g = e["group"]
            if g is None:
                continue
            rt = fleet.tenants[e["tenant"]]
            sig = (rt.adapter.name, rt.spec.exec.precision,
                   rt.spec.exec.sweep_mode, tuple(g["sweep_sig"]))
            if sig in seen_sigs and g["engine"]["compiles"] > 0:
                problems.append(
                    f"tenant {e['tenant']!r} drain {g['group']} recompiled "
                    f"{g['engine']['compiles']} program(s) for an "
                    "already-seen family signature (cross-tenant program "
                    "sharing regressed)")
            seen_sigs.add(sig)
        # per-tenant scanned-dispatch and precision gates (same contracts
        # as the single-tenant path)
        for name, rt in fleet.tenants.items():
            want_prec = rt.spec.exec.precision
            for g in rt.group_log:
                eng = g["engine"]
                if rt.spec.exec.sweep_mode == "scanned":
                    if eng.get("sweep_mode") != "scanned":
                        problems.append(
                            f"tenant {name!r} drain {g['group']} fell back "
                            f"to the {eng.get('sweep_mode')!r} drive loop "
                            "although the deployment requested the scanned "
                            "megaprogram")
                    elif eng.get("sweep_launches") != 1:
                        problems.append(
                            f"tenant {name!r} drain {g['group']} ran "
                            f"{eng.get('sweep_launches')} sweep-program "
                            "launches — a coalesced drain must be exactly "
                            "one")
                if eng.get("precision") != want_prec:
                    problems.append(
                        f"tenant {name!r} drain {g['group']} ran the "
                        f"{eng.get('precision')!r} path although the tenant "
                        f"requested precision={want_prec!r} (silent "
                        "fallback)")
        # tenant-isolation + compile-once gate: replay a tenant that was
        # warmed by a same-family sibling ALONE on a fresh cache — it must
        # end bit-identical (no cross-tenant state bleed) and its fresh
        # cache must compile exactly the programs the WHOLE fleet compiled
        # for that family (N same-family tenants == the N=1 compile set)
        pick = _shared_family_tenant(fleet, fspec)
        if pick is None:
            problems.append(
                "--check on a fleet needs at least two same-family tenants "
                "with at least one drain (cross-tenant sharing and "
                "isolation are otherwise unobservable) — add a same-arch "
                "tenant to the fleet spec")
        else:
            solo, rt_solo = _solo_replay(fleet, fspec, pick, args)
            rt_fleet = fleet.tenants[pick]
            n_fleet = _family_program_count(fleet, rt_fleet.adapter.name)
            n_solo = _family_program_count(solo, rt_solo.adapter.name)
            if n_fleet != n_solo:
                problems.append(
                    f"family {rt_fleet.adapter.name!r}: the fleet's shared "
                    f"cache holds {n_fleet} compiled program(s) but a "
                    f"single-tenant replay compiles {n_solo} — the "
                    "same-family compile count is NOT independent of "
                    "tenant count")
            if not _trees_bitwise_equal(rt_fleet.params, rt_solo.params):
                problems.append(
                    f"tenant {pick!r}: params after interleaved fleet "
                    "drains differ bitwise from a solo replay — tenant "
                    "isolation broken")
            if rt_fleet.unlearner is not None \
                    and rt_solo.unlearner is not None \
                    and not _trees_bitwise_equal(
                        rt_fleet.unlearner.fisher_global,
                        rt_solo.unlearner.fisher_global):
                problems.append(
                    f"tenant {pick!r}: global Fisher after interleaved "
                    "fleet drains differs bitwise from a solo replay — "
                    "tenant isolation broken")
        # cold-start gate (process-global cache, same as single-tenant)
        cold = _cold_start_problem(cache_info)
        if cold:
            problems.append(cold)
        if problems:
            _t.log("serve", "FLEET CHECK FAILED: " + "; ".join(problems))
            raise SystemExit(1)
        cache_stats = fleet.programs.stats()
        _t.log("serve",
               f"fleet check ok: {len(fleet.tenants)} tenant(s), "
               f"{sum(rt.groups for rt in fleet.tenants.values())} drain "
               f"group(s), {cache_stats['compiles']} program compiles / "
               f"{cache_stats['hits']} shared-cache hits across "
               f"{cache_stats['sessions']} engine session(s); tenant "
               f"{pick!r} solo replay bit-identical")
    return result


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an ascending list (0 when empty)."""
    if not sorted_vals:
        return 0.0
    i = min(int(round(q * (len(sorted_vals) - 1))), len(sorted_vals) - 1)
    return sorted_vals[i]


def _weights_report(initial, served) -> Dict:
    """Where the served weights ended up: whether any leaf differs from the
    initial tree, whether every float leaf is finite, and the platforms of
    the devices holding them.  Reductions run on the device; the host reads
    one flag per leaf."""
    before = jax.tree_util.tree_leaves(initial)
    after = jax.tree_util.tree_leaves(served)
    return {
        "changed": any(bool(jnp.any(a != b)) for a, b in zip(before, after)),
        "finite": all(bool(jnp.all(jnp.isfinite(b))) for b in after
                      if jnp.issubdtype(b.dtype, jnp.floating)),
        "platforms": sorted({d.platform for b in after
                             for d in b.devices()}),
    }


def _main_stream(args, cfg, params, tokens, domains, seq_len: int,
                 cache_info: Dict) -> dict:
    """--serve-mode stream: the continuous-batching engine with shadow
    drains and step-deadline publication (DESIGN.md §15)."""
    serve = ServeSpec(cache_dir=args.cache_dir,
                      refresh_every=args.fisher_refresh,
                      sweep_mode=args.sweep_mode,
                      precision=args.precision,
                      publish="step",
                      max_batch=args.max_batch,
                      admit_chunk=args.admit_chunk,
                      publish_lag=args.publish_lag,
                      tau=args.tau)
    svc = ForgetService(cfg, tokens, domains, seq_len, serve=serve)
    eng = StreamEngine(params, cfg, gen_len=args.gen_len,
                       prompt_len=args.prompt_len,
                       max_batch=serve.max_batch,
                       admit_chunk=serve.admit_chunk,
                       publish_lag=serve.publish_lag,
                       service=svc)
    # the burst schedule lives on the ENGINE-STEP clock in stream mode:
    # one legacy "batch" is roughly gen_len decode steps
    if args.unlearn_after >= 0:
        for i, burst in enumerate(_parse_bursts(args)):
            for d in burst:
                svc.submit(d, due_batch=(args.unlearn_after + i)
                           * args.gen_len)
    n_seq = 3 * args.requests   # the batch path's traffic volume
    prompts = np.asarray(tokens[:, :args.prompt_len])
    for i in range(n_seq):
        eng.enqueue(i, prompts[i % len(prompts)])
    t0 = time.time()
    results = eng.run()
    lat = sorted(eng.step_wall)
    result = {
        "serve_mode": "stream",
        "sequences": len(results),
        "tokens": int(sum(r.size for r in results.values())),
        "steps": eng.step,
        "elapsed_s": round(time.time() - t0, 3),
        "publications": eng.publications,
        "drain_aborts": eng.aborts,
        "drain_abort_log": [{"guard": a.get("guard"),
                             "detail": str(a.get("detail"))}
                            for a in svc.abort_log],
        "dead_letters": svc.scheduler.dead(),
        "params_version": svc.params_version,
        "weights": _weights_report(params, svc.params),
        # host loop seconds of a step (no device sync: not decode time)
        "step_host_p50_ms": round(_percentile(lat, 0.50) * 1e3, 4),
        "step_host_p99_ms": round(_percentile(lat, 0.99) * 1e3, 4),
        "engine_counters": eng.stats(),
        "drain_counters": svc.stats(),
        "decode_compile_signatures": eng.decode_cache_size(),
        "unlearn_requests": svc.log,
        "group_log": svc.group_log,
        "coalesced_groups": svc.groups,
        "sweeps": svc.sweeps,
        "engine_stats": (dict(svc.unlearner.stats)
                         if svc.unlearner is not None else {}),
        "unlearn_spec": svc.spec.to_dict(),
        "serve_spec": serve.to_dict(),
        "compilation_cache": _close_cache(cache_info),
    }
    _t.log("serve", f"stream done: {json.dumps(result)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if args.check:
        problems = []
        if len(results) != n_seq:
            problems.append(f"stream served {len(results)} of {n_seq} "
                            "enqueued sequences")
        if eng.decode_cache_size() != 1:
            problems.append(
                f"decode step compiled {eng.decode_cache_size()} "
                "signatures — publications must replay the ONE warm "
                "decode program")
        if args.unlearn_after >= 0 and svc.groups != eng.publications:
            problems.append(
                f"{svc.groups} drain group(s) ran but {eng.publications} "
                "publication(s) happened — a shadow sweep's result was "
                "dropped or double-published")
        if svc.scheduler.pending():
            problems.append(f"{svc.scheduler.pending()} forget request(s) "
                            "still queued at shutdown")
        if eng.aborts:
            problems.append(
                f"{eng.aborts} shadow drain(s) aborted (guard violation "
                "or worker exception) — a fault-free serve must never "
                "trip the drain guard")
        if svc.scheduler.dead():
            problems.append(
                f"{svc.scheduler.dead()} forget request(s) dead-lettered "
                "— no request may terminally fail in a fault-free serve")
        if not result["weights"]["finite"]:
            problems.append("the served weights hold non-finite values")
        cold = _cold_start_problem(result["compilation_cache"])
        if cold:
            problems.append(cold)
        if problems:
            _t.log("serve", "STREAM CHECK FAILED: " + "; ".join(problems))
            raise SystemExit(1)
        _t.log("serve",
               f"stream check ok: {len(results)} sequence(s) in "
               f"{eng.step} step(s), {svc.groups} shadow drain group(s), "
               f"{eng.publications} atomic publication(s), one decode "
               "signature")
    return result


def _parse_bursts(args) -> List[List[int]]:
    """Burst k is due at ``--unlearn-after + k``; domains within a burst
    coalesce into one sweep."""
    if args.forget_domains:
        if ";" in args.forget_domains:
            return [[int(d) for d in b.split(",") if d]
                    for b in args.forget_domains.split(";") if b]
        doms = [int(d) for d in args.forget_domains.split(",")]
        return [doms] if args.coalesce else [[d] for d in doms]
    return [[args.forget_domain]]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--full", dest="full", action="store_true",
                      help="serve the registry's full config (published "
                           "widths and depth)")
    size.add_argument("--smoke", dest="full", action="store_false",
                      help="serve the registry's reduced smoke config "
                           "(the default)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--prefill-block", type=int, default=8,
                    help="chunked-prefill block size (tokens per dispatch) "
                         "of the generate loops (--serve-mode batch, "
                         "--fleet); the stream engine picks its own from "
                         "--prompt-len")
    ap.add_argument("--serve-mode", choices=("batch", "stream"),
                    default="batch",
                    help="'batch': the legacy fixed-batch generate loop "
                         "with in-place drains between batches; 'stream': "
                         "the continuous-batching engine — per-step "
                         "admission/eviction over a fixed slot pool, "
                         "drains on a shadow tree, atomic between-steps "
                         "publication (DESIGN.md §15)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="stream mode: decode slot-pool width "
                         "(ServeSpec.max_batch)")
    ap.add_argument("--admit-chunk", type=int, default=4,
                    help="stream mode: fixed admission sub-batch width "
                         "(ServeSpec.admit_chunk)")
    ap.add_argument("--publish-lag", type=int, default=16,
                    help="stream mode: steps between firing a shadow "
                         "drain and its atomic publication deadline "
                         "(ServeSpec.publish_lag)")
    ap.add_argument("--unlearn-after", type=int, default=1,
                    help="first forget burst after this many batches "
                         "(-1: off)")
    ap.add_argument("--forget-domain", type=int, default=1)
    ap.add_argument("--forget-domains", default=None,
                    help="domains to forget: '1,2' = one request per domain "
                         "on consecutive batches; '1,2;3' = bursts (comma "
                         "within a burst, ';' between) — a burst coalesces "
                         "into one sweep (overrides --forget-domain)")
    ap.add_argument("--tau", type=float, default=ServeSpec.tau,
                    help="forget-accuracy target each drain sweeps down to "
                         "(ServeSpec.tau); a negative target sweeps every "
                         "layer")
    ap.add_argument("--coalesce", action="store_true",
                    help="fold a comma list into a single same-due burst")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero unless sweeps == coalesced groups, "
                         "no drain after the first recompiled, and (with a "
                         "warm --cache-dir) a cold start wrote zero new "
                         "cache entries")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent XLA compilation cache directory "
                         "(ExecSpec.cache_dir): cold restarts replay "
                         "compiled programs from disk; "
                         "JAX_COMPILATION_CACHE_DIR, when set, wins, and "
                         "the default is the checkout's .jax_cache")
    ap.add_argument("--fisher-refresh", type=int, default=0,
                    help="refresh the global Fisher I_D every N drains "
                         "(streamed EMA over retain microbatches at the "
                         "edited weights; 0 = keep the one-shot I_D)")
    ap.add_argument("--sweep-mode", choices=("layerwise", "scanned"),
                    default="scanned",
                    help="engine drive loop: 'scanned' lowers each drain "
                         "as ONE whole-sweep program with on-device "
                         "halting (repro.engine.sweep); 'layerwise' is "
                         "the host-driven oracle loop")
    ap.add_argument("--precision", choices=("fp32", "int8"), default="fp32",
                    help="numeric path for the unlearning engine: 'int8' "
                         "drains through the quantised program family "
                         "(int8 weight codes + per-channel scale tables, "
                         "dequant-free dampening, quantization-aware "
                         "halting); 'fp32' is the oracle default")
    ap.add_argument("--fleet", default=None,
                    help="serve a multi-tenant fleet from this FleetSpec "
                         "JSON file (repro.fleet): per-tenant weights, "
                         "queues and Fisher, ONE drain scheduler, ONE "
                         "shared compiled-program cache; the burst/check "
                         "flags apply to every tenant")
    ap.add_argument("--out", default=None,
                    help="write the result JSON to this path")
    args = ap.parse_args(argv)

    if args.fleet:
        return _main_fleet(args)

    spec = configs.get(args.arch)
    if spec.kind != "lm":
        raise ValueError(
            f"serve.py drives an LM decode loop; --arch {args.arch!r} is a "
            f"{spec.kind!r} architecture — pick an LM entry from "
            f"repro.configs")
    # the cache must be live BEFORE the first compile (prefill/decode too,
    # not just the engine) for a cold start to be replayable from disk
    cache_info = _open_cache(args.cache_dir)
    cfg = spec.full if args.full else spec.smoke
    key = jax.random.PRNGKey(0)
    params = LM.init_lm(key, cfg)

    dcfg = LMDataConfig(vocab=cfg.vocab, n_domains=4,
                        seq_len=args.prompt_len + args.gen_len,
                        n_per_domain=16, seed=0)
    tokens, domains = make_lm_domains(dcfg)

    if args.serve_mode == "stream":
        return _main_stream(args, cfg, params, tokens, domains,
                            dcfg.seq_len, cache_info)

    decode_jit = jax.jit(
        lambda p, c, t, pos: LM.decode_step(p, cfg, t, c, pos))

    svc = ForgetService(cfg, tokens, domains, dcfg.seq_len,
                        serve=ServeSpec(
                            cache_dir=args.cache_dir,
                            refresh_every=args.fisher_refresh,
                            sweep_mode=args.sweep_mode,
                            precision=args.precision,
                            tau=args.tau))
    if args.unlearn_after >= 0:
        for i, burst in enumerate(_parse_bursts(args)):
            for d in burst:
                svc.submit(d, due_batch=args.unlearn_after + i)

    served: List[dict] = []
    batches = [tokens[i:i + args.requests, :args.prompt_len]
               for i in range(0, len(tokens) - args.requests,
                              args.requests)][:3]
    for bi, prompts in enumerate(batches):
        t0 = time.time()
        gen = generate(params, cfg, jnp.asarray(prompts), args.gen_len,
                       decode_jit, prefill_block=args.prefill_block)
        entry = {"batch": bi, "latency_s": round(time.time() - t0, 3),
                 "tokens": int(gen.size)}
        served.append(entry)
        _t.emit("request.generate", tenant="default", **entry)
        params, _ = svc.drain(params, bi + 1)
    # flush requests still queued past the last served batch — a forget
    # request must never be silently dropped at shutdown
    params, _ = svc.drain(params, float("inf"))

    done = [r for r in svc.log if "engine" in r]
    last = done[-1] if done else {}
    cache_info = _close_cache(cache_info)
    refresh_info = None
    if args.fisher_refresh > 0:
        refresh_info = {"every_drains": args.fisher_refresh,
                        "refreshes": len(svc.refresh_log),
                        "log": svc.refresh_log,
                        "staleness": svc.staleness_report(params)}
    result = {"served": served, "unlearned": bool(done),
              "unlearn_requests": svc.log,
              "coalesced_groups": svc.groups, "sweeps": svc.sweeps,
              "group_log": svc.group_log,
              "unlearn_stats": {k: last.get(k) for k in
                                ("stopped_at_l", "macs_vs_ssd_pct")},
              "engine_stats": svc.unlearner.stats if svc.unlearner else {},
              "unlearn_spec": svc.spec.to_dict(),
              "serve_spec": svc.serve_spec.to_dict(),
              "compilation_cache": cache_info,
              "fisher_refresh": refresh_info}
    _t.log("serve", f"done: {json.dumps(result)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    if args.check:
        problems = []
        # coalescing gate: ONE engine sweep per drain point, however many
        # requests were due there — a regression to per-request sweeps shows
        # up as several group entries (or sweeps) at the same drain batch
        sweeps_by_batch: Dict = {}
        for g in svc.group_log:
            sweeps_by_batch[g["batch"]] = (sweeps_by_batch.get(g["batch"], 0)
                                           + g["sweeps"])
        for b, n in sorted(sweeps_by_batch.items()):
            if n > 1:
                problems.append(f"drain at batch {b} ran {n} engine sweeps "
                                "— due requests were not coalesced into "
                                "one group")
        seen_sigs = set()
        for g in svc.group_log:
            sig = tuple(g.get("sweep_sig", ()))
            if sig in seen_sigs and g["engine"]["compiles"] > 0:
                problems.append(f"drain {g['group']} recompiled "
                                f"{g['engine']['compiles']} programs for an "
                                "already-seen drain signature "
                                "(warm-session cache regressed)")
            seen_sigs.add(sig)
        # scanned-mode dispatch-count gate: every coalesced drain must be
        # exactly ONE whole-sweep program launch — a fallback to the
        # layerwise loop (or a K x L dispatch regression) shows up as the
        # engine reporting a different sweep_mode / launch count
        if svc.spec.exec.sweep_mode == "scanned":
            for g in svc.group_log:
                eng = g["engine"]
                if eng.get("sweep_mode") != "scanned":
                    problems.append(
                        f"drain {g['group']} fell back to the "
                        f"{eng.get('sweep_mode')!r} drive loop although the "
                        "deployment requested the scanned megaprogram")
                elif eng.get("sweep_launches") != 1:
                    problems.append(
                        f"drain {g['group']} ran "
                        f"{eng.get('sweep_launches')} sweep-program "
                        "launches — a coalesced drain must be exactly one")
        # precision gate: every drain's engine must report the precision the
        # deployment requested — an int8 deployment that silently fell back
        # to the fp32 path reproduces the oracle numerics exactly, so only
        # this explicit tag catches it (DESIGN.md §12)
        want_prec = svc.spec.exec.precision
        for g in svc.group_log:
            got = g["engine"].get("precision")
            if got != want_prec:
                problems.append(
                    f"drain {g['group']} ran the {got!r} path although the "
                    f"deployment requested precision={want_prec!r} (silent "
                    "fallback)")
        if (want_prec == "int8" and svc.spec.exec.sweep_mode == "scanned"
                and svc.unlearner.stats.get("int8_sweep_launches", 0) < 1):
            problems.append(
                "precision='int8' with the scanned megaprogram never "
                "launched an int8_sweep program (int8 family unused)")
        cold = _cold_start_problem(cache_info)
        if cold:
            problems.append(cold)
        # streamed-refresh gates: the refresh ran between drains, every
        # refresh after the first replayed the cached program (zero
        # compiles), and the refreshed I_D beats the stale snapshot against
        # a from-scratch recompute at the final weights
        if refresh_info is not None:
            if refresh_info["refreshes"] == 0:
                problems.append(
                    f"--fisher-refresh {args.fisher_refresh} was set but no "
                    "refresh ran between drains")
            for i, r in enumerate(svc.refresh_log[1:], start=1):
                if r["engine"]["refresh_compiles"] > 0:
                    problems.append(
                        f"fisher refresh {i} recompiled "
                        f"{r['engine']['refresh_compiles']} refresh "
                        "program(s) (warm refresh family regressed)")
            stale = refresh_info["staleness"]
            if stale is not None and not stale["improved"]:
                problems.append(
                    f"refreshed I_D is NOT closer to the from-scratch "
                    f"recompute at the edited weights (stale rel err "
                    f"{stale['stale_rel_err']:.4f}, refreshed "
                    f"{stale['refreshed_rel_err']:.4f}) — the streamed "
                    "refresh failed its staleness oracle")
        if problems:
            _t.log("serve", "CHECK FAILED: " + "; ".join(problems))
            raise SystemExit(1)
        n_req = sum(g["requests"] for g in svc.group_log)
        extra = ""
        if refresh_info is not None:
            stale = refresh_info["staleness"] or {}
            extra = (f"; {refresh_info['refreshes']} fisher refresh(es), "
                     f"I_D rel err "
                     f"{stale.get('stale_rel_err', float('nan')):.4f}"
                     f" -> {stale.get('refreshed_rel_err', float('nan')):.4f}")
        mode = svc.spec.exec.sweep_mode
        _t.log("serve",
               f"check ok: {n_req} request(s) in {svc.groups} "
               f"group(s), one {mode} sweep per drain, zero recompiles "
               f"after the first drain{extra}")
    return result


if __name__ == "__main__":
    main()
