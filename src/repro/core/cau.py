"""Context-Adaptive Unlearning (Algorithm 1) + Balanced Dampening (Eq. 5/6).

Control structure mirrors the FiCABU processor: the HOST plays the RISC-V
Rocket core (layer loop, checkpoint decisions, early stop), while each
per-layer step — backward GEMMs, Fisher square-accumulate (FIMD IP),
select/beta/multiply (Dampening IP) — runs as ONE fused jitted device
program via the compiled engine (``repro.engine``, see DESIGN.md).
``context_adaptive_unlearn_legacy`` keeps the original three-programs-per-
layer driver as the numerical oracle and benchmark baseline.

Key properties implemented exactly as in the paper:
  * one initial forward pass on the forget batch, caching the INPUT activation
    of every layer (``acts[j]``);
  * layers are processed back-to-front (paper index l=1 == head);
  * Fisher importance comes from a single backward sweep with the ORIGINAL
    weights (see DESIGN.md for the pre/post-edit backprop note);
  * at checkpoints, forget accuracy is evaluated by PARTIAL inference — the
    cached activation at the current layer is pushed through the already-
    edited suffix only (front layers are untouched, so the cache is valid);
  * if forget accuracy <= tau, the remaining front-end layers are skipped.

MACs are accounted on the host exactly as the paper normalises them
(checkpoint overhead included).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import MacCounter
from .schedule import checkpoint_set, sigmoid_profile
from .ssd import dampen_tree

F32 = jnp.float32
Params = Any


@dataclasses.dataclass
class ModelAdapter:
    """Uniform per-layer view of a model for the CAU driver.

    Depth index j runs FRONT (0: stem/embedding) to BACK (n_layers-1: head);
    the paper's back-to-front index is l = n_layers - j.
    """
    name: str
    n_layers: int
    # forward_collect(params, inputs) -> (logits, [acts_0 .. acts_{L-1}])
    forward_collect: Callable[[Params, Any], Tuple[jax.Array, List[jax.Array]]]
    # apply_layer(params, j, layer_p, act) -> next activation (logits for j=L-1)
    apply_layer: Callable[[Params, int, Params, jax.Array], jax.Array]
    get_layer: Callable[[Params, int], Params]
    set_layer: Callable[[Params, int, Params], Params]
    loss: Callable[[jax.Array, jax.Array], jax.Array]       # (logits, labels)
    acc: Callable[[jax.Array, jax.Array], jax.Array]
    layer_fwd_macs: Sequence[int]                           # per-sample fwd MACs
    int_input_layer0: bool = False                          # token-id inputs
    exclude: Optional[Callable[[str], bool]] = None         # param paths to skip
    # --- engine hooks (repro.engine): program-cache sharing across layers ---
    # layer_key(j) -> hashable kind; layers with equal kind AND equal shapes
    # must compute the same function of (ctx, layer_p, act) so one compiled
    # fused step serves all of them. None: every depth is its own kind.
    layer_key: Optional[Callable[[int], Any]] = None
    # layer_ctx(params, j) -> traced context apply_layer needs beyond the
    # layer's own params (None when the layer is self-contained). When the
    # hook itself is None the engine passes the FULL params tree — always
    # correct, never baked into the program as constants.
    layer_ctx: Optional[Callable[[Any, int], Any]] = None


@dataclasses.dataclass(frozen=True)
class UnlearnConfig:
    alpha: float = 10.0
    lam: float = 1.0
    tau: float = 0.05                 # target (random-guess) forget accuracy
    checkpoint_every: int = 4         # paper: every 4 convs (RN) / 3 blocks (ViT)
    balanced: bool = False            # Balanced Dampening on/off
    b_r: float = 10.0
    c_m: Optional[float] = None       # None -> midpoint (or supply from SSD stats)
    chunk_size: int = 8               # Fisher gradient chunking
    use_kernel: bool = False          # Pallas dampening path
    max_layers: Optional[int] = None  # optionally bound the sweep
    # "layerwise": the host drives the per-layer loop (the oracle path);
    # "scanned": lower the whole back-end-first sweep as ONE lax.scan
    # program with on-device halting (repro.engine.sweep) when the layer
    # stack is shape-uniform — heterogeneous stacks fall back automatically.
    sweep_mode: str = "layerwise"
    # "fp32" (default, the oracle) or "int8": the paper's INT8 GEMM-centric
    # pipeline — per-channel symmetric weight quantisation, dampening in the
    # quantised domain, halting on dequantised partial accumulators
    # (DESIGN.md §12). Contract: within optim.compression.INT8_SWEEP_RTOL of
    # the fp32 path per layer, same halt depth on the smoke models.
    precision: str = "fp32"
    quant_min_scale: float = 1e-12    # q8 scale-table clamp (QuantSpec.min_scale)

    def __post_init__(self):
        if self.sweep_mode not in ("layerwise", "scanned"):
            raise ValueError(
                f"UnlearnConfig.sweep_mode must be 'layerwise' or "
                f"'scanned', got {self.sweep_mode!r} — a mistyped mode "
                f"would silently run the layerwise loop")
        if self.precision not in ("fp32", "int8"):
            raise ValueError(
                f"UnlearnConfig.precision must be 'fp32' or 'int8', got "
                f"{self.precision!r} — a mistyped precision would silently "
                f"run the fp32 path")
        if not (isinstance(self.quant_min_scale, float)
                and np.isfinite(self.quant_min_scale)
                and self.quant_min_scale > 0.0):
            raise ValueError(
                f"UnlearnConfig.quant_min_scale must be a finite float > 0 "
                f"(the int8 scale-table clamp), got {self.quant_min_scale!r}")


def _layer_param_counts(adapter: ModelAdapter, params: Params) -> List[int]:
    """Element count of each paper layer's parameters (the MAC statistics'
    ``layer_params``), from shapes alone: the layer views are traced
    abstractly, so no device op runs and ``params`` may be a tree of
    ``jax.ShapeDtypeStruct``."""
    layers = jax.eval_shape(
        lambda p: [adapter.get_layer(p, j) for j in range(adapter.n_layers)],
        params)
    return [sum(x.size for x in jax.tree_util.tree_leaves(sub))
            for sub in layers]


def _chunk(x, cs):
    return jax.tree_util.tree_map(
        lambda a: a.reshape(a.shape[0] // cs, cs, *a.shape[1:]), x)


@partial(jax.jit, static_argnums=(0,))
def _logit_cotangents(loss: Callable, logits_c: jax.Array, labels_c: jax.Array):
    """Per-chunk dL/dlogits for chunk-mean loss. [nc, cs, ...]."""
    def g(lg, lb):
        return jax.grad(lambda z: loss(z, lb))(lg)
    return jax.vmap(g)(logits_c, labels_c)


def _sweep_layer(apply_fn: Callable, layer_p: Params, acts_c, cot_c,
                 with_act_grad: bool):
    """Backward through one layer for every chunk (sequential scan: memory
    stays O(|layer|)). Returns (fisher_layer, cotangents for previous layer).
    """
    fish0 = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), layer_p)

    if with_act_grad:
        def step(fish, inp):
            a, c = inp
            _, vjp_fn = jax.vjp(apply_fn, layer_p, a)
            g_lp, g_a = vjp_fn(c)
            fish = jax.tree_util.tree_map(
                lambda f, g: f + g.astype(F32) ** 2, fish, g_lp)
            return fish, g_a

        fish, g_acts = jax.lax.scan(step, fish0, (acts_c, cot_c))
    else:
        def step(fish, inp):
            a, c = inp
            _, vjp_fn = jax.vjp(lambda lp: apply_fn(lp, a), layer_p)
            (g_lp,) = vjp_fn(c)
            fish = jax.tree_util.tree_map(
                lambda f, g: f + g.astype(F32) ** 2, fish, g_lp)
            return fish, 0.0

        fish, g_acts = jax.lax.scan(step, fish0, (acts_c, cot_c))
        g_acts = None
    nc = jax.tree_util.tree_leaves(acts_c)[0].shape[0]
    fish = jax.tree_util.tree_map(lambda f: f / nc, fish)
    return fish, g_acts


def _restore_excluded(exclude: Callable[[str], bool], new: Params, old: Params):
    """Undo dampening on excluded parameter paths (e.g. MoE routers)."""
    flat_new, treedef = jax.tree_util.tree_flatten_with_path(new)
    flat_old = jax.tree_util.tree_leaves(old)
    out = []
    for (path, leaf), old_leaf in zip(flat_new, flat_old):
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append(old_leaf if exclude(key) else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def context_adaptive_unlearn(
        adapter: ModelAdapter, params: Params, fisher_global: Params,
        inputs: Any, labels: jax.Array, cfg: UnlearnConfig,
        session=None,
) -> Tuple[Params, Dict]:
    """Algorithm 1 (+ optional Balanced Dampening). Returns (params', stats).

    Routes through the ``repro.api.Unlearner`` facade over the compiled
    engine (``repro.engine.UnlearnSession``): one fused device program per
    unique layer shape, checkpoint evaluation as a single traced-depth
    program, and a program cache that persists on ``session`` so repeated
    forget requests retrace nothing. Pass a warm ``session`` (serving path)
    to reuse compiled executables across requests; otherwise an ephemeral
    one is created.
    """
    from repro.api import Unlearner  # deferred: api imports cau
    unl = Unlearner(adapter, fisher_global, session=session)
    new_params, stats = unl.forget((inputs, labels), params=params, cfg=cfg)
    stats.pop("mode", None)  # this entry point predates modes
    return new_params, stats


def context_adaptive_unlearn_legacy(
        adapter: ModelAdapter, params: Params, fisher_global: Params,
        inputs: Any, labels: jax.Array, cfg: UnlearnConfig,
) -> Tuple[Params, Dict]:
    """The pre-engine reference driver: THREE device programs per layer (vjp
    sweep, Fisher square-accumulate, dampen) plus one fresh jit per
    checkpoint depth, all retraced on every call. Kept as the bit-exactness
    oracle for the engine (tests/test_engine.py) and the baseline for
    benchmarks/kernels_bench.py — do not use in serving paths."""
    L = adapter.n_layers
    cps = (set(checkpoint_set(L, cfg.checkpoint_every))
           if 0 < cfg.checkpoint_every <= L else set())
    S = (sigmoid_profile(L, cfg.b_r, cfg.c_m) if cfg.balanced
         else np.ones(L))

    prm_counts = _layer_param_counts(adapter, params)
    macs = MacCounter(adapter.layer_fwd_macs, prm_counts,
                      batch=int(jax.tree_util.tree_leaves(labels)[0].shape[0]))

    # Step 0: one forward pass, cache per-layer input activations.
    logits, acts = adapter.forward_collect(params, inputs)
    macs.add_forward_all()

    cs = cfg.chunk_size
    labels_c = _chunk(labels, cs)
    cot = _logit_cotangents(adapter.loss, _chunk(logits, cs), labels_c)

    stats: Dict[str, Any] = {
        "stopped_at_l": L, "checkpoints_hit": [], "selected_per_layer": {},
        "forget_acc_trace": [], "profile_S": S.tolist(),
    }
    orig = params
    sweep_limit = cfg.max_layers or L

    partial_fns: Dict[int, Callable] = {}

    def partial_inference(j: int):
        """Forward cached act[j] through edited layers j..L-1 -> forget acc."""
        if j not in partial_fns:
            def run(prm, act, lbl):
                x = act
                for jj in range(j, L):
                    x = adapter.apply_layer(prm, jj, adapter.get_layer(prm, jj), x)
                return adapter.acc(x, lbl)
            partial_fns[j] = jax.jit(run)
        return partial_fns[j]

    for l in range(1, min(L, sweep_limit) + 1):   # paper index, back-to-front
        j = L - l
        layer_p = adapter.get_layer(orig, j)       # ORIGINAL weights for vjp

        with_act = j > 0  # no activation cotangent needed past the front layer
        apply_fn = (lambda lp, a, _j=j: adapter.apply_layer(orig, _j, lp, a))
        acts_c = _chunk(acts[j], cs)
        fish, g_acts = _sweep_layer(apply_fn, layer_p, acts_c, cot, with_act)
        macs.add_backward_layer(j)
        macs.add_fisher_layer(j)

        # --- Dampening (SSD rule, optionally depth-scaled) ---
        s = float(S[l - 1])
        fg_layer = adapter.get_layer(fisher_global, j)
        new_layer, masks = dampen_tree(adapter.get_layer(params, j), fish,
                                       fg_layer, cfg.alpha * s, cfg.lam * s,
                                       use_kernel=cfg.use_kernel)
        if adapter.exclude is not None:
            new_layer = _restore_excluded(adapter.exclude, new_layer,
                                          adapter.get_layer(params, j))
        params = adapter.set_layer(params, j, new_layer)
        macs.add_dampen_layer(j)
        stats["selected_per_layer"][l] = int(
            sum(int(jnp.sum(m)) for m in jax.tree_util.tree_leaves(masks)))

        cot = g_acts  # cotangent for the next (more frontal) layer

        # --- Checkpoint: partial inference with cached activations ---
        if l in cps:
            a_forget = float(partial_inference(j)(params, acts[j], labels))
            macs.add_partial_inference(j, L)
            stats["checkpoints_hit"].append(l)
            stats["forget_acc_trace"].append((l, a_forget))
            if a_forget <= cfg.tau:
                stats["stopped_at_l"] = l
                break
    else:
        stats["stopped_at_l"] = min(L, sweep_limit)

    stats["macs"] = macs.total
    stats["macs_ssd"] = MacCounter.ssd_total(adapter.layer_fwd_macs, prm_counts,
                                             macs.batch)
    stats["macs_vs_ssd_pct"] = 100.0 * macs.total / max(stats["macs_ssd"], 1)
    return params, stats
