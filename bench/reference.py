"""Plain float32 reference of a FiCABU drain, and the arithmetic every
model family's reference shares.

Written from the published descriptions, in straightforward ``jax.numpy``
with every matrix product at ``Precision.HIGHEST``; it imports nothing of
the program and reads only weights the benchmark made (``weights.py``).
The model itself is the family's (``bench/families/<family>.py``): its
embedding (the leaf ``embed``, a lookup table), its blocks as ``Segment``s
in front-to-back order, its head (``HEAD_LEAVES`` and ``head``), and its
``Shape``.  This file walks them.

Drain (FiCABU over SSD, Foster et al. AAAI'24 + Balanced Dampening): the
diagonal Fisher of each parameter is the mean over chunks of the squared
gradient of the chunk's mean token cross-entropy, all gradients taken at the
weights before the drain (one backward sweep); a parameter is selected when
``I_forget > alpha_l * I_global`` and scaled by
``min(lam_l * I_global / I_forget, 1)``, where paper layer ``l`` counts from
the head (1) to the embedding (L) and ``alpha_l, lam_l`` are ``alpha, lam``
times the sigmoid profile ``S(l)`` rising from 1 to ``b_r``.  The global
Fisher uses the same estimator with a z-loss term on a retain sample.  With
a negative forget-accuracy target no checkpoint can halt the sweep, so every
layer is edited; a non-negative target is not implemented here.

Everything runs layer by layer so that it fits beside nothing else on one
chip.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
EMBED = "embed"


class Segment(NamedTuple):
    """Consecutive blocks that share one block function and one layout:
    ``apply(lw, x, sh)`` applies one block with leaves ``lw`` to
    x [..., S, D]; ``leaves`` maps each of its leaf names to the key of the
    weights dict that holds that leaf of all ``n`` blocks, stacked
    ``[n, ...]``."""
    apply: Callable
    leaves: Dict[str, str]
    n: int


def n_blocks(fam, sh) -> int:
    return sum(s.n for s in fam.segments(sh))


# ---------------------------------------------------------------------------
# Arithmetic the families share
# ---------------------------------------------------------------------------
def mm(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(F32), precision=HI)


def fp8(a, axis: int):
    """Round through float8 e4m3, scaled so that each slice along ``axis``
    reaches the format's largest value (448), and back to float32."""
    a = a.astype(F32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def quant(w):
    """A weight matrix in fp8, one scale per output channel."""
    return fp8(w, -2)


def mm8(x, w):
    """A product in fp8: the activations one scale per row, the weights
    already rounded by ``quant``."""
    return mm(fp8(x, -1), w)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, pos, theta):
    """x [..., S, H, dh], pos [S]: rotate the two halves of each head."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def xent(logits, labels, z_loss: float):
    """Mean token cross-entropy (+ z-loss on the log-partition)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + z_loss * lse ** 2)


@jax.jit
def token_gaps(logits, next_tokens, mask):
    """Per position: how far the given next token's logit lies below the
    best logit (0 where ``mask`` is off)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, next_tokens[:, None], axis=-1)[:, 0]
    return jnp.where(mask, best - got, 0.0)


@jax.jit
def control_gaps(ref_logits, low_logits, mask):
    """Per position: the gap, under the reference, of the token that the
    lower precision puts first."""
    return token_gaps(ref_logits, jnp.argmax(low_logits, axis=-1), mask)


# ---------------------------------------------------------------------------
# The Fisher sweep and the drain
# ---------------------------------------------------------------------------
def _stacks(fam, w, sh) -> Tuple[List[Segment], Tuple[Dict[str, Any], ...]]:
    segs = fam.segments(sh)
    return segs, tuple({k: w[key] for k, key in s.leaves.items()}
                       for s in segs)


@partial(jax.jit, static_argnums=(0, 4))
def _collect(applies, stacks, emb, tokens, sh):
    """Each segment's block inputs [n, N, S, D] and the final hidden
    state."""
    x = emb.astype(F32)[tokens]
    acts = []
    for apply, ws in zip(applies, stacks):
        def body(x, lw, apply=apply):
            return apply(lw, x, sh), x

        x, a = jax.lax.scan(body, x, ws)
        acts.append(a)
    return tuple(acts), x


def _chunks(a, cs):
    return a.reshape((a.shape[0] // cs, cs) + a.shape[1:])


def _square_mean(fish, g, nc):
    return jax.tree_util.tree_map(lambda f, x: f + x.astype(F32) ** 2 / nc,
                                  fish, g)


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def _head_pass(head, hw, x_fin, labels, sh, cs: int, z_loss: float):
    xc, lc = _chunks(x_fin, cs), _chunks(labels, cs)
    nc = xc.shape[0]

    def body(fish, inp):
        x, lab = inp
        g_hw, g_x = jax.grad(
            lambda p, a: xent(head(p, a, sh), lab, z_loss),
            argnums=(0, 1))(hw, x)
        return _square_mean(fish, g_hw, nc), g_x

    fish0 = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32), hw)
    return jax.lax.scan(body, fish0, (xc, lc))


def _block_grads(apply, lw, acts, cot, sh, cs: int):
    """Fisher of one block and the cotangent of its input, per chunk."""
    ac = _chunks(acts, cs)
    nc = ac.shape[0]

    def body(fish, inp):
        a, c = inp
        _, vjp = jax.vjp(lambda p, x: apply(p, x, sh), lw, a)
        g_lw, g_a = vjp(c)
        return _square_mean(fish, g_lw, nc), g_a

    fish0 = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32), lw)
    return jax.lax.scan(body, fish0, (ac, cot))


@partial(jax.jit, static_argnums=(0, 5, 6))
def _block_pass(apply, ws, j, acts, cot, sh, cs: int):
    return _block_grads(apply, {k: a[j] for k, a in ws.items()}, acts, cot,
                        sh, cs)


@partial(jax.jit, static_argnums=(3,))
def _embed_pass(emb, tokens, cot, cs: int):
    tc = _chunks(tokens, cs)
    nc = tc.shape[0]

    def body(fish, inp):
        t, c = inp
        g = jnp.zeros(emb.shape, F32).at[t].add(c)
        return fish + g ** 2 / nc, None

    fish, _ = jax.lax.scan(body, jnp.zeros(emb.shape, F32), (tc, cot))
    return fish


def fisher_sweep(fam, w, tokens, labels, sh, cs: int, z_loss: float,
                 visit: Callable[[str, Optional[Tuple[int, int, int]],
                                  Dict[str, Any]], None]) -> None:
    """Back to front, hand each layer's Fisher to ``visit(kind, at, fish)``:
    ``("head", None, ...)``, ``("block", (j, s, r), ...)`` for the blocks
    j = L-1 .. 0 (row ``r`` of segment ``s``; the Fisher keyed by the
    block's own leaf names), then ``("embed", None, ...)``.  All gradients
    are taken at ``w``."""
    segs, stacks = _stacks(fam, w, sh)
    acts, x_fin = _collect(tuple(s.apply for s in segs), stacks, w[EMBED],
                           tokens, sh)
    hw = {k: w[k] for k in fam.HEAD_LEAVES}
    fish, cot = _head_pass(fam.head, hw, x_fin, labels, sh, cs, z_loss)
    visit("head", None, fish)
    j = n_blocks(fam, sh)
    for s in range(len(segs) - 1, -1, -1):
        for r in range(segs[s].n - 1, -1, -1):
            j -= 1
            fish, cot = _block_pass(segs[s].apply, stacks[s], r, acts[s][r],
                                    cot, sh, cs)
            visit("block", (j, s, r), fish)
    visit("embed", None, {EMBED: _embed_pass(w[EMBED], tokens, cot, cs)})


def global_fisher(fam, w, tokens, sh, cs: int, z_loss: float
                  ) -> Dict[str, jax.Array]:
    """The global Fisher I_D over a retain sample (rows of tokens), keyed
    and stacked as the weights are."""
    out: Dict[str, Any] = {}
    rows: Dict[Tuple[int, int], Dict[str, jax.Array]] = {}

    def visit(kind, at, fish):
        if kind == "block":
            rows[at[1:]] = fish
        else:
            out.update(fish)

    fisher_sweep(fam, w, tokens[:, :-1], tokens[:, 1:], sh, cs, z_loss,
                 visit)
    for s, seg in enumerate(fam.segments(sh)):
        for k, key in seg.leaves.items():
            out[key] = jnp.stack([rows[s, r][k] for r in range(seg.n)])
    return out


def profile(n_layers: int, b_r: float) -> np.ndarray:
    """Balanced-Dampening S(l), l = 1 (head) .. L (embedding)."""
    L = n_layers
    c_m = (1 + L) / 2.0
    l = np.arange(1, L + 1, dtype=np.float64)
    sig = 1.0 / (1.0 + np.exp(-(l - c_m)))
    return 1.0 + (b_r - 1.0) * (sig - sig[0]) / (sig[-1] - sig[0])


def dampen(theta, i_f, i_g, alpha, lam):
    """SSD Eqs. (3)-(4) on one tensor."""
    i_f, i_g = i_f.astype(F32), i_g.astype(F32)
    sel = i_f > alpha * i_g
    beta = jnp.minimum(lam * i_g / jnp.maximum(i_f, 1e-30), 1.0)
    new = jnp.where(sel, theta.astype(F32) * beta, theta.astype(F32))
    return new.astype(theta.dtype)


@jax.jit
def _dampen_layer(w, i_f, i_g, alpha, lam):
    """Dampen every leaf of one layer; also each leaf's RMS forget
    gradient."""
    new = {k: dampen(w[k], i_f[k], i_g[k], alpha, lam) for k in w}
    rms = {k: jnp.sqrt(jnp.mean(i_f[k])) for k in w}
    return new, rms


@jax.jit
def _row(tree, j):
    return {k: a[j] for k, a in tree.items()}


def _scalars(n_blocks: int, unl: Dict[str, Any]) -> np.ndarray:
    Lu = n_blocks + 2
    S = profile(Lu, float(unl["b_r"]))
    out = np.empty((Lu, 2), np.float32)
    for l in range(1, Lu + 1):
        out[l - 1] = (unl["alpha"] * float(S[l - 1]),
                      unl["lam"] * float(S[l - 1]))
    return out


def stop_layer(n_blocks: int, unl: Dict[str, Any]) -> int:
    """The paper layer a drain stops at.  With a negative target no
    checkpoint's forget accuracy (>= 0) can reach it: the sweep runs to the
    embedding, L = blocks + 2."""
    if unl["tau"] >= 0:
        raise NotImplementedError(
            "the reference drain implements full sweeps only (tau < 0)")
    return n_blocks + 2


def drain(fam, w, i_g, forget, sh, unl: Dict[str, Any],
          grad_rms: Optional[Dict[str, np.ndarray]] = None):
    """One drain of one forget set (rows of tokens) on weights ``w``.
    Returns the edited weights and the paper layer the sweep stopped at.
    ``grad_rms``, when given, receives each leaf's root-mean-square forget
    gradient per row of its stack (one row for a leaf outside the blocks),
    for the rule that leaves out leaves whose gradient is nought to
    rounding."""
    Lu = stop_layer(n_blocks(fam, sh), unl)
    sc = _scalars(Lu - 2, unl)
    new = dict(w)
    segs, stacks = _stacks(fam, w, sh)
    _, stacks_g = _stacks(fam, i_g, sh)
    rows: Dict[str, List] = {key: [None] * s.n
                             for s in segs for key in s.leaves.values()}

    def note(rms, n=1, row=0):
        if grad_rms is None:
            return
        for k, v in rms.items():
            grad_rms.setdefault(k, np.zeros(n))[row] = float(v)

    def visit(kind, at, fish):
        if kind == "head":
            a, lam = sc[0]
            got, rms = _dampen_layer({k: w[k] for k in fam.HEAD_LEAVES},
                                     fish,
                                     {k: i_g[k] for k in fam.HEAD_LEAVES},
                                     a, lam)
            new.update(got)
            note(rms)
        elif kind == "embed":
            a, lam = sc[Lu - 1]
            got, rms = _dampen_layer({EMBED: w[EMBED]}, fish,
                                     {EMBED: i_g[EMBED]}, a, lam)
            new.update(got)
            note(rms)
        else:
            j, s, r = at
            a, lam = sc[Lu - (j + 1) - 1]
            got, rms = _dampen_layer(_row(stacks[s], r), fish,
                                     _row(stacks_g[s], r), a, lam)
            leaves = segs[s].leaves
            for k, v in got.items():
                rows[leaves[k]][r] = v
            note({leaves[k]: v for k, v in rms.items()}, segs[s].n, r)

    fisher_sweep(fam, w, forget[:, :-1], forget[:, 1:], sh,
                 int(unl["fisher_chunk"]), 0.0, visit)
    for key, r in rows.items():
        new[key] = jnp.stack(r)
    return new, Lu
