"""Whole-sweep on-device megaprogram — the scanned back-end-first sweep.

The layerwise engine (``UnlearnSession.forget``) still re-enters Python once
per layer and blocks on a host sync at every halt checkpoint: a warm L-layer
sweep is ``O(L)`` dispatches plus ``O(L / checkpoint_every)`` host round
trips.  The paper's processor streams the WHOLE back-end-first sweep through
its GEMM pipeline with the RISC-V core out of the per-element loop; this
module is the software analogue.  For shape-uniform layer stacks (LM / ViT)
the entire sweep lowers as ONE jitted program:

  * the forget-batch forward (activation collection) and the logit
    cotangents run inside the program — no separate dispatch;
  * the carried edit state is stacked into leading-``[L_sweep, ...]``
    arrays, and the back-to-front walk (vjp + Fisher square-accumulate +
    dampen, cotangent threading between layers) is a ``lax.scan`` whose xs
    are the S(l)-scaled ``(alpha, lam)`` rows plus the reference layers and
    global Fisher of that scan's layers — stacked per scan, so at published
    widths only one segment's copies are live on the device;
  * layer KINDS may differ (gemma3's local/global pattern) as long as
    shapes agree: the walk runs one scan per CONTIGUOUS same-kind segment,
    each body applying one representative apply-closure per kind — sound by
    the engine's ``layer_key`` contract (equal kind + equal shapes => same
    function of ``(ctx, layer_p, act)``), and bit-stable where a
    traced-index ``lax.switch`` is not (its vjp reassociates at ULP level);
  * halt checkpoints are evaluated ON DEVICE inside the scan: partial
    inference runs as a masked forward over the carried (already edited)
    suffix stack, and once ``a_forget <= tau`` the set's ``active`` flag
    drops — later layers become identity through the mask, no host sync
    mid-sweep.  ``stopped_at_l``, per-layer selection counts and the
    forget-accuracy trace come back as scan outputs, read once at the end;
  * K coalesced forget sets ride the SAME program: per-set vjp/Fisher are
    ``vmap``-ed over the set axis against the drain-point snapshot, while
    dampening edits compose set-by-set onto the shared carried layer —
    exactly the split-edit semantics of ``forget_many`` — so a K-domain
    drain is ONE program launch instead of ``K x L`` dispatches.

Heterogeneous stacks (ResNet's per-stage shapes, adapters without a compact
``layer_ctx``) are detected by ``plan_scanned_sweep`` returning None and the
session falls back to the layerwise driver, which stays the bit-exactness
oracle (tests/test_sweep.py).  See DESIGN.md §11 for the stacking contract
and the dispatch/memory argument.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Hashable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cau import (ModelAdapter, _chunk, _logit_cotangents,
                            _restore_excluded)
from repro.core.ssd import dampen_q8_tree, dampen_tree
from repro.optim.compression import (q8_dequantize_tree, q8_fakequant_tree,
                                     q8_quantize_tree)

from .fused import _note_trace, grad_fisher_chunks, shape_signature

F32 = jnp.float32
I32 = jnp.int32
Params = Any
# named scope of the halt-checkpoint evaluations (head, in-scan suffix walk,
# full-tree walk): it reaches each op's HLO ``op_name``, so a device trace
# can attribute their time
CHECKPOINT_SCOPE = "checkpoint"


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Static structure of a scannable stack: the distinct middle-layer
    kinds (in first-seen order), one representative depth per kind (its
    apply-closure serves every layer of that kind), and each middle layer's
    kind index, front-to-back (``type_ids[j - 1]`` for depth ``j``)."""
    n_layers: int
    kinds: Tuple[Hashable, ...]
    rep_depths: Tuple[int, ...]
    type_ids: Tuple[int, ...]

    @property
    def cache_fields(self) -> Hashable:
        return (self.n_layers, self.kinds, self.type_ids)


def plan_scanned_sweep(adapter: ModelAdapter, params: Params,
                       inputs: Any) -> Optional[SweepPlan]:
    """Decide whether the scanned megaprogram can serve this (adapter,
    params, inputs) — None means "use the layerwise driver".

    Eligible when the middle layers (depths 1..L-2) are SHAPE-uniform:
    equal param subtree signatures, equal block input/output activation
    shapes (the head input included, so cotangents thread through one scan
    carry), and self-contained (``layer_ctx`` returns None — the head may
    still carry a context, e.g. tied embeddings).  Activation shapes come
    from ``jax.eval_shape`` on the adapter's forward — no compute spent on
    an ineligible model.
    """
    L = adapter.n_layers
    if L < 3:
        return None
    if adapter.layer_key is None or adapter.layer_ctx is None:
        return None
    # blocks (and the front layer) must be self-contained: the scan applies
    # them from the stacked carry with no side context
    for j in range(0, L - 1):
        if adapter.layer_ctx(params, j) is not None:
            return None
    sig0 = shape_signature(adapter.get_layer(params, 1))
    for j in range(2, L - 1):
        if shape_signature(adapter.get_layer(params, j)) != sig0:
            return None
    try:
        _, acts = jax.eval_shape(adapter.forward_collect, params, inputs)
    except Exception:
        return None
    ref = acts[1]
    if not all(a.shape == ref.shape and a.dtype == ref.dtype
               for a in acts[1:L]):
        return None
    kinds: list = []
    reps: list = []
    type_ids: list = []
    for j in range(1, L - 1):
        k = adapter.layer_key(j)
        if k not in kinds:
            kinds.append(k)
            reps.append(j)
        type_ids.append(kinds.index(k))
    return SweepPlan(n_layers=L, kinds=tuple(kinds), rep_depths=tuple(reps),
                     type_ids=tuple(type_ids))


def effective_tau32(tau: float) -> np.float32:
    """The f32 threshold that makes the on-device halt test ``a <= tau32``
    EXACTLY equivalent to the layerwise host test ``float(a) <= tau`` (f64):
    the largest f32 value that is <= tau."""
    t = np.float32(tau)
    if float(t) > float(tau):
        t = np.nextafter(t, np.float32(-np.inf))
    return t


def build_sweep_program(adapter: ModelAdapter, plan: SweepPlan, *,
                        n_sets: int,
                        cps: Tuple[int, ...],
                        limit: int,
                        chunk_size: int,
                        use_kernel: bool,
                        mesh=None,
                        mesh_sharding: str = "tp",
                        precision: str = "fp32",
                        quant_min_scale: float = 1e-12,
                        tag: str = "sweep") -> Callable:
    """Build the whole-sweep program.  Returns a jitted

        prog(ref_tree, edit_tree, fisher, inputs_k, labels_k, scalars, tau)
            -> (new_edit_tree, stop_l [K] i32, n_sel [K, limit] i32,
                acc_trace [K, limit] f32)

    ``ref_tree`` is the vjp/Fisher snapshot (== ``edit_tree`` for a single
    request), ``inputs_k``/``labels_k`` are length-K tuples of per-set
    arrays (all sets shape-equal), ``scalars`` is the ``[limit, 2]`` f32
    table of S(l)-scaled ``(alpha, lam)`` rows (traced — Balanced-Dampening
    profile changes never retrace), ``tau`` the f32 halt threshold from
    ``effective_tau32``.  ``cps`` (paper-l checkpoint set), ``limit``
    (bounded sweep depth) and ``chunk_size`` are static and part of the
    session's cache key.  ``acc_trace`` rows hold NaN at non-checkpoint
    layers; entries past a set's ``stop_l`` are scratch the host discards.

    ``precision="int8"`` builds the quantised program family (DESIGN.md
    §12): ``ref_tree`` must arrive ALREADY fake-quantised — materialised by
    the driver's cached fakequant program, never re-quantised here (q8 is
    not ULP-idempotent, and an in-trace fakequant would let XLA fuse the
    dequant multiply into the vjp GEMMs, perturbing the Fisher against the
    layerwise oracle).  vjp/Fisher and the forward collect run on those
    deployed weights, the carried edit state is stacked ``[Lb, ...]`` int8
    code arrays
    plus stacked f32 scale tables walked by the SAME ``lax.scan``, and
    dampening edits the codes dequant-free.  Halt checkpoints DEQUANTISE the
    carried suffix on the fly before the masked partial forward, so the tau
    compare sees the accuracy of the deployable dequantised weights — paired
    with ``effective_tau32`` this keeps the int8 halt depth aligned with
    fp32 on the smoke models (regression-pinned).  The returned tree is the
    dequantised deployment state (every layer fake-quantised, edited or
    not); fp32 stays the default and the oracle.
    """
    if precision not in ("fp32", "int8"):
        raise ValueError(
            f"build_sweep_program precision must be 'fp32' or 'int8', got "
            f"{precision!r}")
    int8 = precision == "int8"
    L = plan.n_layers
    Lb = L - 2
    K = n_sets
    cs = chunk_size
    cps_set = frozenset(cps)
    n_scan = max(0, min(limit, L - 1) - 1)   # paper l = 2 .. min(limit, L-1)
    exclude = adapter.exclude

    def apply_branch(rep_j: int):
        def br(lp, a, _j=rep_j):
            return adapter.apply_layer(None, _j, lp, a)
        return br

    branches = tuple(apply_branch(j) for j in plan.rep_depths)

    # Mixed-kind stacks (gemma3's local/global pattern) are walked as one
    # lax.scan per CONTIGUOUS same-kind segment, each body applying its
    # kind's closure DIRECTLY — a single traced-index lax.switch would be
    # one scan, but its vjp reassociates at the ULP level and would break
    # bit-exactness against the layerwise oracle.  Segment count is static
    # and small (the block pattern's period), and the whole chain still
    # lowers into the one jitted program.
    segs: list = []                  # back-to-front: (kind, [paper l ...])
    for l in range(2, 2 + n_scan):
        t = plan.type_ids[L - l - 1]
        if segs and segs[-1][0] == t:
            segs[-1][1].append(l)
        else:
            segs.append((t, [l]))
    runs: list = []                  # front-to-back: (kind, s0, s1)
    for sidx, t in enumerate(plan.type_ids):
        if runs and runs[-1][0] == t:
            runs[-1] = (t, runs[-1][1], sidx + 1)
        else:
            runs.append((t, sidx, sidx + 1))

    def _stack(tree, depths=range(1, L - 1)):
        subs = [adapter.get_layer(tree, j) for j in depths]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *subs)

    def _constrain_stack(tree):
        if mesh is None:
            return tree
        from jax.sharding import NamedSharding

        from repro.dist import sharding as shd
        specs = shd.stacked_param_pspecs(tree, mesh, mode=mesh_sharding)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)), tree, specs)

    def _per_set(fn, *args_k):
        """Apply ``fn`` per forget set: direct for K == 1 (bit-exact with
        the layerwise single-request path), vmapped over the set axis for a
        coalesced drain."""
        if K == 1:
            out = fn(*(a[0] for a in args_k))
            return jax.tree_util.tree_map(lambda x: x[None], out)
        return jax.vmap(fn)(*args_k)

    # int8 edits happen on the CODES (dequant-free, shared math with the
    # fused step's _body_q); exclusion restores pre-edit codes either way.
    _damp = dampen_q8_tree if int8 else dampen_tree

    def _dampen_compose(cur, fish_k, fish_g, sc, active):
        """Split-edit composition: each set's dampening (selection from ITS
        snapshot Fisher) multiplies onto the shared carried layer, in set
        order, masked by that set's halting flag."""
        n_sel_k = []
        for k in range(K):
            fish = jax.tree_util.tree_map(lambda x: x[k], fish_k)
            new_layer, masks = _damp(cur, fish, fish_g, sc[0], sc[1],
                                     use_kernel=use_kernel)
            if exclude is not None:
                new_layer = _restore_excluded(exclude, new_layer, cur)
            n_sel_k.append(sum(jnp.sum(m).astype(I32)
                               for m in jax.tree_util.tree_leaves(masks)))
            ak = active[k]
            cur = jax.tree_util.tree_map(
                lambda n, o: jnp.where(ak, n, o), new_layer, cur)
        return cur, jnp.stack(n_sel_k)

    def _suffix_acc(stack_cur, stack_s, stack_like, head_cur, ctx_head, bidx,
                    x0, labels):
        """Partial inference for one set: the cached activation at block
        ``bidx`` pushed through the already-edited suffix (masked forward
        over the carried stack, one scan per same-kind run) and the edited
        head.  Quantization-aware halting: when the carry holds int8 codes
        (``stack_s`` is the stacked scale-table tree, else None) each
        segment is dequantised on the fly, so the tau compare runs on the
        DEQUANTISED partial accumulator — the accuracy of the weights that
        would actually be deployed."""
        x = x0
        for (t, s0, s1) in runs:
            if int8:
                seg = jax.tree_util.tree_map(
                    lambda q, s, e: (q[s0:s1].astype(F32)
                                     * s[s0:s1]).astype(e.dtype),
                    stack_cur, stack_s, stack_like)
            else:
                seg = jax.tree_util.tree_map(lambda a: a[s0:s1], stack_cur)

            def blk(xx, inp, _t=t):
                lp, sidx = inp
                y = branches[_t](lp, xx)
                return jnp.where(sidx >= bidx, y, xx), None

            x, _ = jax.lax.scan(blk, x,
                                (seg, jnp.arange(s0, s1, dtype=I32)))
        logits = adapter.apply_layer(ctx_head, L - 1, head_cur, x)
        return adapter.acc(logits, labels)

    def _unchunk(x):
        """[K, nc, cs, ...] -> [K, nc*cs, ...]: the inverse of ``_chunk``
        per set (a pure reshape, bit-identical to the original batch)."""
        return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])

    def sweep(ref_tree, edit_tree, fisher, inputs_k, labels_k, scalars, tau):
        _note_trace(tag)
        # int8 contract: ref_tree is the fake-quantised snapshot, already
        # materialised by the driver (the weights the int8 deployment
        # executes) — quantising it in-trace would perturb the vjp GEMMs at
        # the ULP level vs the layerwise oracle (see docstring)
        ref_run = ref_tree
        # ---- forward collect + cotangents (on-device, per set) ------------
        acts_rows = []          # per set: [L-1 entries][nc, cs, ...], j >= 1
        cot0 = []
        for inp, lbl in zip(inputs_k, labels_k):
            logits, acts = adapter.forward_collect(ref_run, inp)
            cot0.append(_logit_cotangents(adapter.loss, _chunk(logits, cs),
                                          _chunk(lbl, cs)))
            acts_rows.append([_chunk(a, cs) for a in acts[1:]])
        inputs0_c = jnp.stack([_chunk(i, cs) for i in inputs_k])
        labels_s = jnp.stack(labels_k)
        cot = jnp.stack(cot0)                       # [K, nc, cs, ...]
        # block-input activations, chunked: [K, Lb, nc, cs, ...]; head input
        # (depth L-1) kept separate for the prologue
        acts_mid = jnp.stack([jnp.stack(r[:Lb]) for r in acts_rows])
        acts_head = jnp.stack([r[Lb] for r in acts_rows])

        # only the carried edit state is stacked whole (checkpoints walk the
        # edited suffix); the vjp reference and the global Fisher are
        # stacked per segment below and stream in as that scan's xs
        edit_stack = _constrain_stack(_stack(edit_tree))
        if int8:
            # the carried edit state: stacked int8 codes + stacked f32
            # per-(layer, channel) scale tables — lead_axes=2 over the
            # [Lb, ...] layout yields bit-identical scales to quantising
            # each layer alone, so the layerwise int8 driver stays the
            # bit-exactness oracle for this program too
            stack_q, stack_s = q8_quantize_tree(edit_stack, lead_axes=2,
                                                min_scale=quant_min_scale)
        else:
            stack_q = stack_s = None
        # two head contexts, mirroring the layerwise oracle: the vjp/Fisher
        # side reads the SNAPSHOT tree (forget_many pins statistics to the
        # drain point), while checkpoints evaluate against the EDIT tree —
        # the weights that would actually be deployed (under tied
        # embeddings the two differ whenever reference != params); in int8
        # "deployed" means fake-quantised, for the checkpoint context too
        ctx_head = adapter.layer_ctx(ref_run, L - 1)
        ctx_head_cp = adapter.layer_ctx(
            q8_fakequant_tree(edit_tree, min_scale=quant_min_scale)
            if int8 else edit_tree, L - 1)
        head_ref = adapter.get_layer(ref_run, L - 1)
        head_cur = adapter.get_layer(edit_tree, L - 1)
        if int8:
            head_q, head_s = q8_quantize_tree(head_cur,
                                              min_scale=quant_min_scale)
            head_edit = head_q
        else:
            head_edit = head_cur
        fish_head = adapter.get_layer(fisher, L - 1)

        active = jnp.ones((K,), bool)
        stop_l = jnp.full((K,), I32(min(L, limit)))
        n_sel_rows = []
        acc_rows = []
        nan_row = jnp.full((K,), jnp.nan, F32)

        # ---- l = 1: the head --------------------------------------------
        def head_grads(a_c, c_c):
            return grad_fisher_chunks(
                lambda lp, aa: adapter.apply_layer(ctx_head, L - 1, lp, aa),
                head_ref, a_c, c_c, with_act_grad=True)

        fish_k, g_k = _per_set(head_grads, acts_head, cot)
        head_edit, n_sel = _dampen_compose(head_edit, fish_k, fish_head,
                                           scalars[0], active)
        # the deployable head: dequantised codes in int8, the edit itself in
        # fp32 — checkpoints, the suffix walk and the output tree all read it
        head_cp = (q8_dequantize_tree(head_edit, head_s, like=head_cur)
                   if int8 else head_edit)
        cot = g_k
        n_sel_rows.append(n_sel)
        if 1 in cps_set:
            def head_acc(x0, lbl):
                logits = adapter.apply_layer(ctx_head_cp, L - 1, head_cp,
                                             x0)
                return adapter.acc(logits, lbl)

            with jax.named_scope(CHECKPOINT_SCOPE):
                a_f = _per_set(head_acc, _unchunk(acts_head), labels_s)
            halted = active & (a_f <= tau)
            stop_l = jnp.where(halted, I32(1), stop_l)
            active = active & ~halted
            acc_rows.append(a_f)
        else:
            acc_rows.append(nan_row)

        # ---- l = 2 .. min(limit, L-1): the scanned block stack ----------
        def make_body(apply_fn):
            def body(carry, xs):
                stack_cur, cot_c, act, st = carry
                bidx, sc, is_cp, l_now, ref_layer, fish_g = xs
                a_c = acts_mid[:, bidx]

                def mid_grads(a_one, c_one):
                    return grad_fisher_chunks(
                        apply_fn, ref_layer, a_one, c_one,
                        with_act_grad=True)

                fish_k, g_k = _per_set(mid_grads, a_c, cot_c)
                cur = jax.tree_util.tree_map(
                    lambda x: x[bidx], stack_cur)
                cur, n_sel = _dampen_compose(cur, fish_k, fish_g, sc, act)
                stack_cur = jax.tree_util.tree_map(
                    lambda s, c: s.at[bidx].set(c), stack_cur, cur)
                cot_c = jnp.where(act.reshape((K,) + (1,) * (cot_c.ndim - 1)),
                                  g_k, cot_c)

                def do_cp(_):
                    def one(x0, lbl):
                        return _suffix_acc(stack_cur, stack_s, edit_stack,
                                           head_cp, ctx_head_cp, bidx, x0,
                                           lbl)
                    with jax.named_scope(CHECKPOINT_SCOPE):
                        return _per_set(one, _unchunk(a_c), labels_s)

                a_f = jax.lax.cond(is_cp, do_cp,
                                   lambda _: nan_row, None)
                halted = is_cp & act & (a_f <= tau)
                st = jnp.where(halted, l_now, st)
                act = act & ~halted
                return (stack_cur, cot_c, act, st), (n_sel, a_f)
            return body

        carry = (stack_q if int8 else edit_stack, cot, active, stop_l)
        for t, seg_ls in segs:
            bidx_arr = jnp.asarray([L - l - 1 for l in seg_ls], I32)
            iscp_arr = jnp.asarray([l in cps_set for l in seg_ls], bool)
            sc_arr = scalars[seg_ls[0] - 1:seg_ls[-1]]
            depths = [L - l for l in seg_ls]
            # the barrier orders this segment's stacking after the previous
            # segment's scan, so only one segment's copies are live at once
            ref_b, fish_b, carry = jax.lax.optimization_barrier(
                (ref_run, fisher, carry))
            carry, (ns, af) = jax.lax.scan(
                make_body(branches[t]), carry,
                (bidx_arr, sc_arr, iscp_arr, jnp.asarray(seg_ls, I32),
                 _constrain_stack(_stack(ref_b, depths)),
                 _constrain_stack(_stack(fish_b, depths))))
            n_sel_rows.extend(ns[i] for i in range(len(seg_ls)))
            acc_rows.extend(af[i] for i in range(len(seg_ls)))
        stack_out, cot, active, stop_l = carry
        if int8:
            # the output tree is the dequantised deployment state — also for
            # layers the sweep never edited (their codes are untouched, so
            # this is exactly fakequant of the pristine layer)
            stack_out = q8_dequantize_tree(stack_out, stack_s,
                                           like=edit_stack)

        # ---- l = L: the front layer (embedding / patch / stem) ----------
        new_tree = edit_tree
        if limit >= L:
            front_ref = adapter.get_layer(ref_run, 0)
            front_cur = adapter.get_layer(edit_tree, 0)
            if int8:
                front_q, front_s = q8_quantize_tree(
                    front_cur, min_scale=quant_min_scale)
                front_edit = front_q
            else:
                front_edit = front_cur
            fish_front = adapter.get_layer(fisher, 0)

            def front_grads(a_c, c_c):
                return grad_fisher_chunks(
                    lambda lp, aa: adapter.apply_layer(None, 0, lp, aa),
                    front_ref, a_c, c_c, with_act_grad=False)

            fish_k, _ = _per_set(front_grads, inputs0_c, cot)
            front_edit, n_sel = _dampen_compose(front_edit, fish_k,
                                                fish_front,
                                                scalars[L - 1], active)
            n_sel_rows.append(n_sel)
            front_out = (q8_dequantize_tree(front_edit, front_s,
                                            like=front_cur)
                         if int8 else front_edit)
            new_tree = adapter.set_layer(new_tree, 0, front_out)
        elif int8:
            # bounded sweep: the front layer is never edited but still ships
            # quantised in the int8 deployment state
            new_tree = adapter.set_layer(
                new_tree, 0,
                q8_fakequant_tree(adapter.get_layer(edit_tree, 0),
                                  min_scale=quant_min_scale))
        new_tree = adapter.set_layer(new_tree, L - 1, head_cp)
        for sidx in range(Lb):
            new_tree = adapter.set_layer(
                new_tree, sidx + 1,
                jax.tree_util.tree_map(lambda x: x[sidx], stack_out))
        if limit >= L and L in cps_set:
            # final checkpoint: the generic full-tree walk (the front edit
            # may feed later layers — tied embeddings — so contexts are
            # rebuilt from the edited tree, exactly as the layerwise
            # per-depth program does)
            def full_acc(inp, lbl):
                x = inp
                for jj in range(L):
                    x = adapter.apply_layer(new_tree, jj,
                                            adapter.get_layer(new_tree, jj),
                                            x)
                return adapter.acc(x, lbl)

            with jax.named_scope(CHECKPOINT_SCOPE):
                a_f = _per_set(full_acc, jnp.stack(inputs_k), labels_s)
            halted = active & (a_f <= tau)
            stop_l = jnp.where(halted, I32(L), stop_l)
            active = active & ~halted
            acc_rows.append(a_f)
        elif limit >= L:
            acc_rows.append(nan_row)

        n_sel_out = jnp.stack(n_sel_rows, axis=1)        # [K, limit]
        acc_out = jnp.stack(acc_rows, axis=1)            # [K, limit]
        return new_tree, stop_l, n_sel_out, acc_out

    return jax.jit(sweep)


def sweep_cache_key(plan: SweepPlan, adapter: ModelAdapter, *,
                    n_sets: int, params: Params, fisher: Params,
                    sets: Sequence[Tuple[Any, Any]],
                    cps: Tuple[int, ...], limit: int,
                    chunk_size: int, use_kernel: bool,
                    precision: str = "fp32",
                    quant_min_scale: float = 1e-12) -> Hashable:
    """The session-cache key for a sweep program: every static quantity the
    builder bakes in.  ``(alpha, lam, tau)`` and the Fisher VALUES are
    traced, so hyperparameter changes and streamed I_D refreshes replay the
    cached executable.  ``precision`` separates the int8 program family from
    fp32 (the session ALSO counts them under distinct compile/hit stats);
    ``quant_min_scale`` is baked into the quantisation closures."""
    return ("sweep", precision, float(quant_min_scale), n_sets,
            plan.cache_fields,
            shape_signature(params), shape_signature(fisher),
            shape_signature(tuple(sets)), cps, limit, chunk_size,
            use_kernel, adapter.exclude is not None)
