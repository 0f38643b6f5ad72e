"""UnlearnSession — the warm, compiled unlearning engine.

Holds the adapter, the global Fisher importance, and a cross-request program
cache so a serving device pays compilation ONCE:

  * fused per-layer steps are cached by (layer kind, shape signature): all
    layers sharing a block shape within one sweep — every ViT/LM block —
    reuse one executable, and the 2nd..Nth forget request retraces nothing;
  * checkpoint partial inference is ONE program with the start depth j as a
    *traced* operand (blocks before j take a lax.cond identity branch), so
    there is no per-j program family at all when layer activations are
    shape-uniform (LM/ViT/enc-dec); heterogeneous models (ResNet) fall back
    to per-depth programs that are still cached across requests.

The host drives the layer loop / checkpoint decisions / early stop exactly
as the RISC-V core drives the paper's processor; everything else is compiled.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cau import (ModelAdapter, UnlearnConfig, _chunk,
                            _layer_param_counts, _logit_cotangents)
from repro.core.metrics import MacCounter
from repro.core.schedule import checkpoint_set, sigmoid_profile
from repro.obs import telemetry as _t
from repro.optim.compression import (q8_dequantize_tree, q8_fakequant_tree,
                                     q8_quantize_tree)

from .fused import _note_trace, build_fused_step, shape_signature
from .programs import ProgramCache
from .sweep import (build_sweep_program, effective_tau32, plan_scanned_sweep,
                    sweep_cache_key)

F32 = jnp.float32
Params = Any


class UnlearnSession:
    """Compiled unlearning engine bound to (adapter, fisher_global).

    ``donate=None`` lets each fused step donate the layer buffer on
    accelerator backends (the in-place edit path); the default ``False`` is
    safe when callers keep references to the pre-edit parameter tree.

    This is the ENGINE layer: call sites should drive it through the
    ``repro.api.Unlearner`` facade (which owns the Fisher lifecycle and the
    session's warmth across requests) rather than constructing sessions
    directly — CI's api-gate enforces that outside repro.api/repro.engine.
    """

    def __init__(self, adapter: ModelAdapter, fisher_global: Params,
                 *, donate: Optional[bool] = False,
                 programs: Optional[ProgramCache] = None):
        self.adapter = adapter
        self.fisher_global = fisher_global
        self.donate = donate
        # mesh placement hints for the scanned-sweep program's stacked
        # [L, ...] trees (set by the facade's shard(); None = single device)
        self.mesh = None
        self.mesh_sharding: str = "tp"
        # compiled-program store: private by default (pre-fleet behavior),
        # or a shared process-level cache so same-family tenants compile
        # each program once.  Keys are namespaced by the adapter FAMILY
        # (name + depth) and the donation regime — sharing never crosses
        # families, and a donating session can never hand a buffer-eating
        # executable to a non-donating one.
        self.programs = programs if programs is not None else ProgramCache()
        self.programs.sessions += 1
        self._ns: Hashable = (adapter.name, adapter.n_layers, donate)
        # counts, and sweep_wait_s: seconds blocked reading the scanned
        # sweep's outputs (the drain.wait span), on telemetry.monotonic
        self.stats: Dict[str, float] = {
            "requests": 0, "group_sweeps": 0,
            "fused_compiles": 0, "fused_hits": 0,
            "partial_compiles": 0, "partial_hits": 0,
            "refresh_compiles": 0, "refresh_hits": 0,
            "sweep_compiles": 0, "sweep_hits": 0, "sweep_launches": 0,
            # the int8 program family keeps its own counters so a silent
            # fp32 fallback is visible: an int8-configured request that
            # bumps sweep_* instead of int8_sweep_* fails the bench gate
            "int8_sweep_compiles": 0, "int8_sweep_hits": 0,
            "int8_sweep_launches": 0,
            "quant_compiles": 0, "quant_hits": 0,
            # per-layer parameter counts for the MAC statistics, memoised
            # per tree shape (built once, then hit on every drain)
            "param_count_builds": 0, "param_count_hits": 0,
            "sweep_wait_s": 0.0,
        }

    # -- program cache ------------------------------------------------------
    def _cached(self, family: str, key: Hashable,
                builder: Callable[[], Callable]) -> Callable:
        """Fetch/compile through the (possibly shared) program cache,
        crediting this SESSION's per-family counters: a program another
        tenant already compiled is a cache hit here — exactly the
        accounting the cross-tenant sharing gates read."""
        prog, compiled = self.programs.get_or_build((self._ns,) + key,
                                                    builder)
        self.stats[f"{family}_compiles" if compiled
                   else f"{family}_hits"] += 1
        return prog

    @property
    def _refresh(self) -> Dict[Hashable, Callable]:
        """This session's live refresh-family entries (lifecycle tests
        count them); keys are the stream-level keys, namespace stripped."""
        return {k[1:]: v for k, v in self.programs._progs.items()
                if k[0] == self._ns and len(k) > 1 and k[1] == "refresh"}

    def _param_counts(self, tree: Params) -> Tuple[int, ...]:
        """Per-paper-layer parameter counts for the MAC statistics, memoised
        per tree shape beside the sweep plan: they depend on shapes alone,
        so a drain reads them without a device op, and same-family tenants
        sharing the program cache count once."""
        built = []

        def build():
            built.append(True)
            return tuple(_layer_param_counts(self.adapter, tree))

        counts = self.programs.plan_or_build(
            (self._ns, "param_counts", shape_signature(tree)), build)
        self.stats["param_count_builds" if built
                   else "param_count_hits"] += 1
        return counts

    def _layer_key(self, j: int) -> Hashable:
        lk = getattr(self.adapter, "layer_key", None)
        return ("j", j) if lk is None else lk(j)

    def _emit_sweep(self, engine: Dict, stops: List[int]) -> None:
        """One ``engine.sweep`` telemetry event per sweep launch — the halt
        depths are the paper's context-adaptivity signal, the compile/hit
        deltas are the warmth signal the load gates watch."""
        _t.emit("engine.sweep", adapter=str(self.adapter.name),
                sets=len(stops), stopped_at_l=list(stops),
                sweep_mode=engine["sweep_mode"],
                precision=engine["precision"],
                compiles=engine["compiles"],
                cache_hits=engine["cache_hits"])

    def _layer_ctx(self, params: Params, j: int) -> Params:
        """Traced context the layer forward needs beyond its own params.
        Adapters that are self-contained per layer return None; the default
        (no hook) passes the full tree, which is always correct."""
        lc = getattr(self.adapter, "layer_ctx", None)
        return params if lc is None else lc(params, j)

    def fused_program(self, j: int, ctx, layer_p, acts_c, cot_c,
                      cfg: UnlearnConfig, *, split_edit: bool = False
                      ) -> Callable:
        """The fused per-layer step for depth j, from cache when the layer's
        kind + shapes were seen before (this request or any earlier one).

        ``split_edit`` selects the coalesced-sweep variant: vjp/Fisher on the
        snapshot layer, dampening applied to the group-edited layer (the edit
        target shares the reference's shape signature, so the cache key only
        differs in the kind prefix)."""
        with_act = j > 0
        kind = ("gfused" if split_edit else "fused") + (
            "8" if cfg.precision == "int8" else "")
        key = (kind, self._layer_key(j), shape_signature(ctx),
               shape_signature(layer_p), shape_signature(acts_c),
               shape_signature(cot_c), with_act, cfg.use_kernel,
               self.adapter.exclude is not None)
        adapter = self.adapter

        def builder():
            def apply_fn(c, lp, a, _j=j):
                return adapter.apply_layer(c, _j, lp, a)

            # split-edit programs never donate: with the default
            # reference=params the first set's edit target IS the snapshot
            # buffer later sets (and this call's vjp) still read — donating
            # it would delete the reference mid-group.
            return build_fused_step(
                apply_fn, with_act_grad=with_act, use_kernel=cfg.use_kernel,
                exclude=adapter.exclude,
                donate=False if split_edit else self.donate,
                split_edit=split_edit,
                precision=cfg.precision,
                tag=f"{kind}:{self._layer_key(j)}")

        return self._cached("fused", key, builder)

    def sweep_program(self, key: Hashable, builder: Callable[[], Callable],
                      *, family: str = "sweep") -> Callable:
        """The scanned whole-sweep family (repro.engine.sweep): one program
        per (set count, stack structure, shape signature, halting schedule).
        ``(alpha, lam, tau)`` and Fisher values are traced operands, so a
        warm serving process replays one executable per drain shape —
        Balanced-Dampening profile changes and streamed I_D refreshes
        included.  ``family`` selects the compile/hit counter pair —
        "sweep" (fp32) or "int8_sweep" (the quantised program family)."""
        return self._cached(family, key, builder)

    def _fakequant_program(self, tree: Params, min_scale: float) -> Callable:
        """Whole-tree per-channel fakequant as ONE cached jitted program —
        the layerwise int8 driver's entry step (the scanned program fuses
        the same op into its own trace)."""
        key = ("quant", shape_signature(tree), float(min_scale))

        def builder():
            def run(t, _ms=float(min_scale)):
                _note_trace("quant")
                return q8_fakequant_tree(t, min_scale=_ms)

            return jax.jit(run)

        return self._cached("quant", key, builder)

    def refresh_program(self, key: Hashable, builder: Callable[[], Callable]
                        ) -> Callable:
        """The streamed-Fisher refresh family (repro.engine.fisher_stream):
        the session hosts these compiled steps next to the fused/checkpoint
        families so ONE warm session owns every program a serving process
        replays, and the zero-retrace lifecycle tests cover all three."""
        return self._cached("refresh", key, builder)

    def evict_refresh_programs(self, token) -> int:
        """Drop every refresh program keyed to ``token`` (a FisherStream's
        ``cache_token``): re-arming a facade's refresh replaces the stream,
        and the dead stream's executables must not accumulate in a
        long-lived session/shared cache.  Scoped to THIS session's
        namespace — a fleet tenant can never evict a sibling's family."""
        ns = self._ns
        return self.programs.evict_where(
            lambda k: (k[0] == ns and len(k) > 2 and k[1] == "refresh"
                       and k[2] is token))

    # -- checkpoint partial inference ---------------------------------------
    def _uniform_suffix(self, acts: List[jax.Array]) -> bool:
        """True when every block input (depths 1..L-2) and the head input
        share shape+dtype, so one traced-j program covers all checkpoints."""
        L = self.adapter.n_layers
        if L < 3:
            return False
        ref = acts[1]
        return all(a.shape == ref.shape and a.dtype == ref.dtype
                   for a in acts[1:L])

    def _suffix_program(self, params, act, labels) -> Callable:
        adapter = self.adapter
        L = adapter.n_layers
        key = ("suffix", shape_signature(params), shape_signature(act),
               shape_signature(labels))

        def builder():
            def run(prm, a, lbl, j):
                _note_trace("suffix")
                x = a
                for jj in range(1, L - 1):
                    lp = adapter.get_layer(prm, jj)

                    def live(xx, _jj=jj, _lp=lp, _prm=prm):
                        return adapter.apply_layer(_prm, _jj, _lp, xx)

                    x = jax.lax.cond(jj >= j, live, lambda xx: xx, x)
                x = adapter.apply_layer(prm, L - 1,
                                        adapter.get_layer(prm, L - 1), x)
                return adapter.acc(x, lbl)

            return jax.jit(run)

        return self._cached("partial", key, builder)

    def _perj_program(self, j: int, params, act, labels) -> Callable:
        adapter = self.adapter
        L = adapter.n_layers
        key = ("partial", j, shape_signature(params), shape_signature(act),
               shape_signature(labels))

        def builder():
            def run(prm, a, lbl, _j=j):
                _note_trace(f"partial:{_j}")
                x = a
                for jj in range(_j, L):
                    x = adapter.apply_layer(prm, jj,
                                            adapter.get_layer(prm, jj), x)
                return adapter.acc(x, lbl)

            return jax.jit(run)

        return self._cached("partial", key, builder)

    def partial_acc(self, j: int, params, act, labels,
                    uniform: bool) -> jax.Array:
        """Forget accuracy by partial inference: the cached activation at
        depth j pushed through the already-edited suffix j..L-1.

        Returns the DEVICE scalar — coercing to a host float here would
        force a blocking sync per checkpoint on every caller; the layerwise
        drive loop coerces exactly once, at the point it actually branches
        on the value, and other readers may keep the result on device."""
        if uniform and j >= 1:
            prog = self._suffix_program(params, act, labels)
            return prog(params, act, labels, jnp.int32(j))
        return self._perj_program(j, params, act, labels)(params, act, labels)

    # -- scanned whole-sweep megaprogram (repro.engine.sweep) ---------------
    def _family_counters(self) -> Tuple[int, int]:
        """(compiles, cache hits) summed over the request-serving program
        families — fused per-layer steps, checkpoint programs, the fp32 and
        int8 scanned whole-sweep families, and the fakequant entry step."""
        s = self.stats
        return (s["fused_compiles"] + s["partial_compiles"]
                + s["sweep_compiles"] + s["int8_sweep_compiles"]
                + s["quant_compiles"],
                s["fused_hits"] + s["partial_hits"] + s["sweep_hits"]
                + s["int8_sweep_hits"] + s["quant_hits"])

    def _try_scanned(self, params: Params,
                     forget_sets: List[Tuple[Any, jax.Array]],
                     cfg: UnlearnConfig,
                     reference: Optional[Params] = None
                     ) -> Optional[Tuple[Params, List[Dict]]]:
        """Run the whole back-end-first sweep as ONE compiled program when
        the layer stack is scannable; None means "fall back to the layerwise
        driver" (heterogeneous stacks like ResNet, adapters without a
        compact layer_ctx, or a ragged drain group).  Per-set halting, MAC
        accounting and the checkpoint trace are reconstructed on the host
        from the program's scan outputs — read once, after the single
        launch."""
        adapter = self.adapter
        K = len(forget_sets)
        sig0 = shape_signature(forget_sets[0])
        if any(shape_signature(s) != sig0 for s in forget_sets[1:]):
            return None  # ragged group: per-set shapes must stack
        pk = (self._ns, "plan", shape_signature(params), sig0)
        plan = self.programs.plan_or_build(
            pk, lambda: plan_scanned_sweep(adapter, params,
                                           forget_sets[0][0]))
        if plan is None:
            return None

        with _t.span("drain.prepare"):
            L = adapter.n_layers
            cps = (tuple(checkpoint_set(L, cfg.checkpoint_every))
                   if 0 < cfg.checkpoint_every <= L else ())
            limit = min(L, cfg.max_layers or L)
            S = (sigmoid_profile(L, cfg.b_r, cfg.c_m) if cfg.balanced
                 else np.ones(L))
            # the same host arithmetic as the layerwise loop: python-float
            # product cast to f32, one (alpha, lam) row per paper layer
            scal = np.empty((limit, 2), np.float32)
            for l in range(1, limit + 1):
                s = float(S[l - 1])
                scal[l - 1, 0] = cfg.alpha * s
                scal[l - 1, 1] = cfg.lam * s

            int8 = cfg.precision == "int8"
            family = "int8_sweep" if int8 else "sweep"
            key = sweep_cache_key(
                plan, adapter, n_sets=K, params=params,
                fisher=self.fisher_global, sets=forget_sets, cps=cps,
                limit=limit, chunk_size=cfg.chunk_size,
                use_kernel=cfg.use_kernel, precision=cfg.precision,
                quant_min_scale=cfg.quant_min_scale
            ) + (self.mesh, self.mesh_sharding)
            prog = self.sweep_program(key, lambda: build_sweep_program(
                adapter, plan, n_sets=K, cps=cps, limit=limit,
                chunk_size=cfg.chunk_size, use_kernel=cfg.use_kernel,
                mesh=self.mesh, mesh_sharding=self.mesh_sharding,
                precision=cfg.precision, quant_min_scale=cfg.quant_min_scale,
                tag=f"sweep{'8' if int8 else ''}:K{K}"), family=family)

            ref_tree = params if reference is None else reference
            if int8:
                # the program's int8 contract: the reference arrives already
                # fake-quantised, materialised by the cached fakequant program
                ref_tree = self._fakequant_program(
                    ref_tree, cfg.quant_min_scale)(ref_tree)
        inputs_k = tuple(s[0] for s in forget_sets)
        labels_k = tuple(s[1] for s in forget_sets)
        with _t.span("drain.sweep"):
            new_params, stop, n_sel, acc = prog(
                ref_tree, params, self.fisher_global, inputs_k, labels_k,
                scal, effective_tau32(cfg.tau))
            self.stats["sweep_launches"] += 1
            if int8:
                self.stats["int8_sweep_launches"] += 1
            # ONE host read for the whole drain — the scan outputs carry
            # every per-set halting/selection/trace quantity
            t0 = _t.monotonic()
            with _t.span("drain.wait"):
                stop = np.asarray(stop)
                n_sel = np.asarray(n_sel)
                acc = np.asarray(acc)
            self.stats["sweep_wait_s"] += _t.monotonic() - t0

        # per-set halting, selection and MAC accounting
        with _t.span("drain.finish"):
            prm_counts = self._param_counts(ref_tree)
            stats_k: List[Dict] = []
            for k in range(K):
                sl = int(stop[k])
                hit = [c for c in cps if c <= sl]
                macs = MacCounter(
                    adapter.layer_fwd_macs, prm_counts,
                    batch=int(jax.tree_util.tree_leaves(
                        labels_k[k])[0].shape[0]))
                macs.add_forward_all()
                for l in range(1, sl + 1):
                    j = L - l
                    macs.add_backward_layer(j)
                    macs.add_fisher_layer(j)
                    macs.add_dampen_layer(j)
                for c in hit:
                    macs.add_partial_inference(L - c, L)
                st: Dict[str, Any] = {
                    "stopped_at_l": sl,
                    "checkpoints_hit": hit,
                    "selected_per_layer": {l: int(n_sel[k, l - 1])
                                           for l in range(1, sl + 1)},
                    "forget_acc_trace": [(c, float(acc[k, c - 1]))
                                         for c in hit],
                    "profile_S": S.tolist(),
                    "macs": macs.total,
                    "macs_ssd": MacCounter.ssd_total(adapter.layer_fwd_macs,
                                                     prm_counts, macs.batch),
                }
                st["macs_vs_ssd_pct"] = (100.0 * st["macs"]
                                         / max(st["macs_ssd"], 1))
                stats_k.append(st)
        return new_params, stats_k

    # -- the drive loop -----------------------------------------------------
    def forget(self, params: Params, inputs: Any, labels: jax.Array,
               cfg: UnlearnConfig) -> Tuple[Params, Dict]:
        """One forget request: Algorithm 1 (+ optional Balanced Dampening)
        through the compiled engine. Returns (params', stats).

        ``cfg.sweep_mode == "scanned"`` routes through the whole-sweep
        megaprogram (repro.engine.sweep) when the layer stack is scannable;
        otherwise (and for ``"layerwise"``) the host drives the per-layer
        loop below, which stays the bit-exactness oracle."""
        adapter = self.adapter
        self.stats["requests"] += 1
        comp0, hits0 = self._family_counters()
        launch0 = self.stats["sweep_launches"]

        if cfg.sweep_mode == "scanned":
            res = self._try_scanned(params, [(inputs, labels)], cfg)
            if res is not None:
                new_params, stats_k = res
                comp1, hits1 = self._family_counters()
                st = stats_k[0]
                st["engine"] = {
                    "compiles": comp1 - comp0, "cache_hits": hits1 - hits0,
                    "uniform_suffix": True, "sweep_mode": "scanned",
                    "precision": cfg.precision,
                    "sweep_launches": self.stats["sweep_launches"] - launch0,
                }
                self._emit_sweep(st["engine"], [st["stopped_at_l"]])
                return new_params, st

        L = adapter.n_layers
        int8 = cfg.precision == "int8"
        pristine = params
        if int8:
            # Weight-only fake-quant deployment state (DESIGN.md §12): every
            # forward/checkpoint runs on fq(params); each layer's edit starts
            # from the PRISTINE f32 layer and is quantised exactly ONCE
            # inside the fused int8 step (q8 is not ULP-idempotent, so the
            # fq working tree must never be re-quantised).
            params = self._fakequant_program(
                params, cfg.quant_min_scale)(params)
        cps = (set(checkpoint_set(L, cfg.checkpoint_every))
               if 0 < cfg.checkpoint_every <= L else set())
        S = (sigmoid_profile(L, cfg.b_r, cfg.c_m) if cfg.balanced
             else np.ones(L))

        prm_counts = self._param_counts(params)
        macs = MacCounter(adapter.layer_fwd_macs, prm_counts,
                          batch=int(jax.tree_util.tree_leaves(labels)[0].shape[0]))

        logits, acts = adapter.forward_collect(params, inputs)
        macs.add_forward_all()
        uniform = self._uniform_suffix(acts)

        cs = cfg.chunk_size
        labels_c = _chunk(labels, cs)
        cot = _logit_cotangents(adapter.loss, _chunk(logits, cs), labels_c)

        stats: Dict[str, Any] = {
            "stopped_at_l": L, "checkpoints_hit": [], "selected_per_layer": {},
            "forget_acc_trace": [], "profile_S": S.tolist(),
        }
        sweep_limit = cfg.max_layers or L

        for l in range(1, min(L, sweep_limit) + 1):  # paper index, back->front
            j = L - l
            layer_p = adapter.get_layer(params, j)  # untouched == original
            ctx = self._layer_ctx(params, j)
            acts_c = _chunk(acts[j], cs)
            s = float(S[l - 1])
            scalars = jnp.asarray([cfg.alpha * s, cfg.lam * s], F32)
            fg_layer = adapter.get_layer(self.fisher_global, j)

            if int8:
                # vjp/Fisher reference = the materialised fq layer (layer_p
                # from the fq working tree); edit codes quantised from the
                # PRISTINE layer, exactly once, outside the step's trace
                edit_q, edit_s = q8_quantize_tree(
                    adapter.get_layer(pristine, j),
                    min_scale=cfg.quant_min_scale)
                step = self.fused_program(j, ctx, layer_p, acts_c, cot, cfg,
                                          split_edit=True)
                new_q, g_acts, n_sel = step(ctx, layer_p, edit_q, fg_layer,
                                            acts_c, cot, scalars)
                new_layer = q8_dequantize_tree(new_q, edit_s, like=layer_p)
            else:
                step = self.fused_program(j, ctx, layer_p, acts_c, cot, cfg)
                new_layer, g_acts, n_sel = step(ctx, layer_p, fg_layer,
                                                acts_c, cot, scalars)
            macs.add_backward_layer(j)
            macs.add_fisher_layer(j)
            macs.add_dampen_layer(j)

            params = adapter.set_layer(params, j, new_layer)
            stats["selected_per_layer"][l] = int(n_sel)
            cot = g_acts if j > 0 else None

            if l in cps:
                # the checkpoint's single host sync: partial_acc hands back
                # the device scalar; coerce once, where we branch on it
                a_forget = float(self.partial_acc(j, params, acts[j], labels,
                                                  uniform))
                macs.add_partial_inference(j, L)
                stats["checkpoints_hit"].append(l)
                stats["forget_acc_trace"].append((l, a_forget))
                if a_forget <= cfg.tau:
                    stats["stopped_at_l"] = l
                    break
        else:
            stats["stopped_at_l"] = min(L, sweep_limit)

        stats["macs"] = macs.total
        stats["macs_ssd"] = MacCounter.ssd_total(adapter.layer_fwd_macs,
                                                 prm_counts, macs.batch)
        stats["macs_vs_ssd_pct"] = 100.0 * macs.total / max(stats["macs_ssd"], 1)
        comp1, hits1 = self._family_counters()
        stats["engine"] = {
            "compiles": comp1 - comp0,
            "cache_hits": hits1 - hits0,
            "uniform_suffix": uniform,
            "sweep_mode": "layerwise",
            "precision": cfg.precision,
        }
        self._emit_sweep(stats["engine"], [stats["stopped_at_l"]])
        return params, stats

    # -- coalesced multi-set sweep ------------------------------------------
    def forget_many(self, params: Params, forget_sets: List[Tuple[Any, jax.Array]],
                    cfg: UnlearnConfig, *, reference: Optional[Params] = None
                    ) -> Tuple[Params, List[Dict], Dict]:
        """Fault-injection shell around the group sweep (DESIGN.md §16).

        ``fault_scope`` (set by the facade to the tenant name; defaults to
        the adapter family) keys which installed ``FaultSpec``s hit this
        session.  Both sites corrupt the CANDIDATE tree only — the caller's
        guard discards it and the live weights never see the damage:

        * ``nan_batch``      a non-finite dampening scale (lam = NaN), the
                             numeric shape of a poisoned forget batch: every
                             selected weight goes NaN (finite guard);
        * ``fisher_corrupt`` the retain Fisher scaled to ~0, so selection
                             grabs everything and beta ~= 0 zeroes it
                             (edit-magnitude guard).  Restored in a finally:
                             the session's Fisher survives the injection.
        """
        import dataclasses as _dc

        from repro.robust import faults as _faults
        scope = getattr(self, "fault_scope", None) or self.adapter.name
        if _faults.fire("nan_batch", scope):
            # alpha=0 widens selection to every weight with forget signal:
            # the NaN scale is guaranteed to land however conservative the
            # deployment's own alpha made the selection mask
            cfg = _dc.replace(cfg, lam=float("nan"), alpha=0.0)
        prev_fisher = None
        if _faults.fire("fisher_corrupt", scope):
            prev_fisher = self.fisher_global
            self.fisher_global = jax.tree_util.tree_map(
                lambda x: x * 1e-12, prev_fisher)
        try:
            return self._forget_many_impl(params, forget_sets, cfg,
                                          reference=reference)
        finally:
            if prev_fisher is not None:
                self.fisher_global = prev_fisher

    def _forget_many_impl(self, params: Params,
                          forget_sets: List[Tuple[Any, jax.Array]],
                          cfg: UnlearnConfig, *,
                          reference: Optional[Params] = None
                          ) -> Tuple[Params, List[Dict], Dict]:
        """One back-to-front sweep serving a GROUP of forget sets.

        ``forget_sets`` is a list of (inputs, labels) pairs — e.g. every
        forget request due at a serving drain point, one per domain. The
        layer stack is walked ONCE: at each layer every still-active set
        runs the split-edit fused step (vjp/Fisher against the drain-point
        snapshot ``reference``, dampening composed onto the group-edited
        layer), so K coalesced requests pay one layer walk, one set of
        cached executables, and one checkpoint program instead of K.

        Per-set halting accounting is preserved: each set keeps its own
        cotangent stream, MAC counter, checkpoint trace and ``stopped_at_l``
        — checkpoints are evaluated against the composed suffix (the weights
        that would actually be deployed), and a set that reaches tau stops
        contributing edits to more frontal layers while the others continue.

        ``reference`` (default: ``params`` at entry) is the statistics
        snapshot: with the default, a coalesced drain is numerically
        identical to sequential per-domain sweeps that share the drain-point
        snapshot for their Fisher/activations (tests/test_engine.py).

        Returns (params', [stats per set], group_stats).
        """
        adapter = self.adapter
        K = len(forget_sets)
        if K < 1:
            raise ValueError("forget_many needs at least one (inputs, "
                             "labels) forget set; skip the drain instead of "
                             "passing an empty group")
        ref_tree = params if reference is None else reference
        self.stats["requests"] += K
        self.stats["group_sweeps"] += 1
        comp0, hits0 = self._family_counters()
        launch0 = self.stats["sweep_launches"]

        if cfg.sweep_mode == "scanned":
            res = self._try_scanned(params, forget_sets, cfg,
                                    reference=reference)
            if res is not None:
                new_params, stats_k = res
                comp1, hits1 = self._family_counters()
                group_stats = {
                    "sets": K, "sweeps": 1,
                    "stopped_at_l": [st["stopped_at_l"] for st in stats_k],
                    "macs": sum(st["macs"] for st in stats_k),
                    "engine": {
                        "compiles": comp1 - comp0,
                        "cache_hits": hits1 - hits0,
                        "uniform_suffix": True,
                        "sweep_mode": "scanned",
                        "precision": cfg.precision,
                        # measured, not asserted: the serve --check gate
                        # compares this against exactly 1 per drain
                        "sweep_launches":
                            self.stats["sweep_launches"] - launch0,
                    },
                }
                self._emit_sweep(group_stats["engine"],
                                 group_stats["stopped_at_l"])
                return new_params, stats_k, group_stats

        L = adapter.n_layers
        cps = (set(checkpoint_set(L, cfg.checkpoint_every))
               if 0 < cfg.checkpoint_every <= L else set())
        S = (sigmoid_profile(L, cfg.b_r, cfg.c_m) if cfg.balanced
             else np.ones(L))
        int8 = cfg.precision == "int8"
        if int8:
            # fq snapshot = the deployed reference every set backprops
            # through; edit codes come from the PRISTINE edit tree, quantised
            # once per layer, composed across the K sets in the q domain, and
            # dequantised once into the fq working tree.
            fqp = self._fakequant_program(ref_tree, cfg.quant_min_scale)
            ref_run = fqp(ref_tree)
            pristine_edit = params
            params = ref_run if reference is None else fqp(params)
        else:
            ref_run = ref_tree
        prm_counts = self._param_counts(ref_tree)
        cs = cfg.chunk_size

        acts_k: List[List[jax.Array]] = []
        cot_k: List[Any] = []
        labels_k: List[jax.Array] = []
        macs_k: List[MacCounter] = []
        stats_k: List[Dict] = []
        for inputs, labels in forget_sets:
            logits, acts = adapter.forward_collect(ref_run, inputs)
            macs = MacCounter(adapter.layer_fwd_macs, prm_counts,
                              batch=int(jax.tree_util.tree_leaves(labels)[0].shape[0]))
            macs.add_forward_all()
            labels_c = _chunk(labels, cs)
            cot_k.append(_logit_cotangents(adapter.loss, _chunk(logits, cs),
                                           labels_c))
            acts_k.append(acts)
            labels_k.append(labels)
            macs_k.append(macs)
            stats_k.append({
                "stopped_at_l": L, "checkpoints_hit": [],
                "selected_per_layer": {}, "forget_acc_trace": [],
                "profile_S": S.tolist(),
            })
        uniform = self._uniform_suffix(acts_k[0])

        active = [True] * K
        sweep_limit = cfg.max_layers or L

        for l in range(1, min(L, sweep_limit) + 1):  # paper index, back->front
            j = L - l
            ref_layer = adapter.get_layer(ref_run, j)   # snapshot == original
            ctx = self._layer_ctx(ref_run, j)
            if int8:
                cur_q, cur_s = q8_quantize_tree(
                    adapter.get_layer(pristine_edit, j),
                    min_scale=cfg.quant_min_scale)
                cur = cur_q
            else:
                cur = adapter.get_layer(params, j)
            s = float(S[l - 1])
            scalars = jnp.asarray([cfg.alpha * s, cfg.lam * s], F32)
            fg_layer = adapter.get_layer(self.fisher_global, j)

            for k in range(K):
                if not active[k]:
                    continue
                acts_c = _chunk(acts_k[k][j], cs)
                step = self.fused_program(j, ctx, ref_layer, acts_c,
                                          cot_k[k], cfg, split_edit=True)
                cur, g_acts, n_sel = step(ctx, ref_layer, cur, fg_layer,
                                          acts_c, cot_k[k], scalars)
                macs_k[k].add_backward_layer(j)
                macs_k[k].add_fisher_layer(j)
                macs_k[k].add_dampen_layer(j)
                stats_k[k]["selected_per_layer"][l] = int(n_sel)
                cot_k[k] = g_acts if j > 0 else None

            if int8:
                # beta <= 1 keeps the scale table valid across all K edits
                cur = q8_dequantize_tree(
                    cur, cur_s, like=adapter.get_layer(pristine_edit, j))
            params = adapter.set_layer(params, j, cur)

            if l in cps:
                for k in range(K):
                    if not active[k]:
                        continue
                    a_forget = float(self.partial_acc(j, params, acts_k[k][j],
                                                      labels_k[k], uniform))
                    macs_k[k].add_partial_inference(j, L)
                    stats_k[k]["checkpoints_hit"].append(l)
                    stats_k[k]["forget_acc_trace"].append((l, a_forget))
                    if a_forget <= cfg.tau:
                        stats_k[k]["stopped_at_l"] = l
                        active[k] = False
                if not any(active):
                    break
        else:
            for k in range(K):
                if active[k]:
                    stats_k[k]["stopped_at_l"] = min(L, sweep_limit)

        for k in range(K):
            st = stats_k[k]
            st["macs"] = macs_k[k].total
            st["macs_ssd"] = MacCounter.ssd_total(adapter.layer_fwd_macs,
                                                  prm_counts, macs_k[k].batch)
            st["macs_vs_ssd_pct"] = 100.0 * st["macs"] / max(st["macs_ssd"], 1)
        comp1, hits1 = self._family_counters()
        group_stats = {
            "sets": K, "sweeps": 1,
            "stopped_at_l": [st["stopped_at_l"] for st in stats_k],
            "macs": sum(st["macs"] for st in stats_k),
            "engine": {
                "compiles": comp1 - comp0,
                "cache_hits": hits1 - hits0,
                "uniform_suffix": uniform,
                "sweep_mode": "layerwise",
                "precision": cfg.precision,
            },
        }
        self._emit_sweep(group_stats["engine"], group_stats["stopped_at_l"])
        return params, stats_k, group_stats
