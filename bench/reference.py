"""Plain float32 reference of the served model and of a FiCABU drain.

Written from the published descriptions, in straightforward ``jax.numpy``
with every matrix product at ``Precision.HIGHEST``; it imports nothing of
the program and reads only weights the benchmark made (``weights.py``).

Model (Llama/Qwen2 family, as the configuration files state): token
embedding; per block ``x + Attn(RMSNorm(x))`` then ``x + SwiGLU(RMSNorm(x))``
with grouped-query attention, optional q/k/v bias and rotary embedding on
the two halves of each head; final RMSNorm and an untied LM head.

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

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
BLOCK_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                "w_gate", "w_up", "w_down")
HEAD_LEAVES = ("final_norm", "lm_head")


class Shape:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, cfg: Dict[str, Any]):
        self.L = cfg["num_hidden_layers"]
        self.D = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.KV = cfg["num_key_value_heads"]
        self.dh = cfg["head_dim"]
        self.V = cfg["vocab_size"]
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.bias = bool(cfg["attention_bias"])

    def __hash__(self):
        return hash(tuple(sorted(self.__dict__.items())))

    def __eq__(self, other):
        return isinstance(other, Shape) and self.__dict__ == other.__dict__


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------
def _mm(x, w):
    return jnp.einsum("...d,df->...f", x, w.astype(F32), precision=HI)


def _fp8(a, axis: int):
    """Round through float8 e4m3, scaled so that each slice along ``axis``
    reaches the format's largest value (448), and back to float32."""
    a = a.astype(F32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _quant(w):
    """A weight matrix in fp8, one scale per output channel."""
    return _fp8(w, -2)


def _mm8(x, w):
    """A product in fp8: the activations one scale per row, the weights
    already rounded by ``_quant``."""
    return _mm(_fp8(x, -1), w)


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


def _qkv(lw, x, pos, sh: Shape, mm=_mm):
    h = rmsnorm(x, lw["ln1"], sh.eps)
    q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
    if sh.bias:
        q = q + lw["bq"].astype(F32)
        k = k + lw["bk"].astype(F32)
        v = v + lw["bv"].astype(F32)
    lead = x.shape[:-1]
    q = rope(q.reshape(lead + (sh.H, sh.dh)), pos, sh.theta)
    k = rope(k.reshape(lead + (sh.KV, sh.dh)), pos, sh.theta)
    return q, k, v.reshape(lead + (sh.KV, sh.dh))


def _attend(q, k, v, sh: Shape):
    """Causal GQA: head h reads key/value head h // (H / KV)."""
    G = sh.H // sh.KV
    S = q.shape[-3]
    qg = q.reshape(q.shape[:-2] + (sh.KV, G, sh.dh))
    s = jnp.einsum("...qkgd,...skd->...kgqs", qg, k, precision=HI)
    s = s / math.sqrt(sh.dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("...kgqs,...skd->...qkgd", p, v, precision=HI)
    return o.reshape(o.shape[:-3] + (sh.H * sh.dh,))


def _ffn(lw, x, sh: Shape, mm=_mm):
    h = rmsnorm(x, lw["ln2"], sh.eps)
    g, u = mm(h, lw["w_gate"]), mm(h, lw["w_up"])
    return x + mm(jax.nn.silu(g) * u, lw["w_down"])


def block(lw, x, sh: Shape):
    """One transformer block on x [..., S, D] at positions 0..S-1."""
    pos = jnp.arange(x.shape[-2])
    q, k, v = _qkv(lw, x, pos, sh)
    x = x + _mm(_attend(q, k, v, sh), lw["wo"])
    return _ffn(lw, x, sh)


def head(hw, x, sh: Shape, mm=_mm):
    return mm(rmsnorm(x, hw["final_norm"], sh.eps), hw["lm_head"])


def _layers(w) -> Dict[str, jax.Array]:
    return {k: w[k] for k in BLOCK_LEAVES if k in w}


def xent(logits, labels, z_loss: float):
    """Mean token cross-entropy (+ z-loss on the log-partition)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + z_loss * lse ** 2)


@partial(jax.jit, static_argnums=(2, 6))
def segment_logits(w, tokens, sh: Shape, kv, done, seg, quant=False):
    """Logits of one sequence whose positions were served by several weight
    versions.  ``tokens`` [T] are its inputs; ``kv`` [L, 2, T, KV, dh] holds
    the keys and values of the positions already computed (``done``) under
    earlier versions; this call computes every position with ``w`` but keeps
    the cached keys/values where ``done``, and returns the logits [T, V]
    (valid at the ``seg`` positions) and the cache with ``seg`` written.
    ``quant`` computes every product with a weight matrix in fp8, weights
    and activations alike (the control).
    """
    q8 = _quant if quant else (lambda a: a)
    mm = _mm8 if quant else _mm
    emb = _fp8(w["embed"], -1) if quant else w["embed"].astype(F32)
    x = emb[tokens]
    pos = jnp.arange(tokens.shape[0])

    def body(x, inp):
        lw, kv_l = inp
        lw = {k: (q8(a) if a.ndim == 2 else a) for k, a in lw.items()}
        q, k, v = _qkv(lw, x, pos, sh, mm)
        k = jnp.where(done[:, None, None], kv_l[0], k)
        v = jnp.where(done[:, None, None], kv_l[1], v)
        x = x + mm(_attend(q, k, v, sh), lw["wo"])
        x = _ffn(lw, x, sh, mm)
        new = jnp.stack([jnp.where(seg[:, None, None], k, kv_l[0]),
                         jnp.where(seg[:, None, None], v, kv_l[1])])
        return x, new

    x, kv = jax.lax.scan(body, x, (_layers(w), kv))
    hw = {"final_norm": w["final_norm"], "lm_head": q8(w["lm_head"])}
    return head(hw, x, sh, mm), kv


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
@partial(jax.jit, static_argnums=(2,))
def _collect(w, tokens, sh: Shape):
    """Block inputs [L, N, S, D] and the final hidden state."""
    x = w["embed"].astype(F32)[tokens]

    def body(x, lw):
        return block(lw, x, sh), x

    x, acts = jax.lax.scan(body, x, _layers(w))
    return acts, x


def _chunks(a, cs):
    return a.reshape((a.shape[0] // cs, cs) + a.shape[1:])


def _square_mean(fish, g, nc):
    return jax.tree_util.tree_map(lambda f, x: f + x.astype(F32) ** 2 / nc,
                                  fish, g)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head_pass(hw, x_fin, labels, sh: Shape, cs: int, z_loss: float):
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


def _block_grads(lw, acts, cot, sh: Shape, cs: int):
    """Fisher of one block and the cotangent of its input, per chunk."""
    ac = _chunks(acts, cs)
    nc = ac.shape[0]

    def body(fish, inp):
        a, c = inp
        _, vjp = jax.vjp(lambda p, x: block(p, x, sh), lw, a)
        g_lw, g_a = vjp(c)
        return _square_mean(fish, g_lw, nc), g_a

    fish0 = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32), lw)
    return jax.lax.scan(body, fish0, (ac, cot))


@partial(jax.jit, static_argnums=(4, 5))
def _block_pass(ws, j, acts, cot, sh: Shape, cs: int):
    return _block_grads({k: a[j] for k, a in ws.items()}, acts, cot, sh, cs)


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


def fisher_sweep(w, tokens, labels, sh: Shape, cs: int, z_loss: float,
                 visit: Callable[[str, Optional[int], Dict[str, Any]], None]
                 ) -> None:
    """Back to front, hand each layer's Fisher to ``visit(kind, j, fish)``:
    ``("head", None, ...)``, ``("block", j, ...)`` for j = L-1 .. 0, then
    ``("embed", None, ...)``.  All gradients are taken at ``w``."""
    acts, x_fin = _collect(w, tokens, sh)
    hw = {k: w[k] for k in HEAD_LEAVES}
    fish, cot = _head_pass(hw, x_fin, labels, sh, cs, z_loss)
    visit("head", None, fish)
    ws = _layers(w)
    for j in range(sh.L - 1, -1, -1):
        fish, cot = _block_pass(ws, j, acts[j], cot, sh, cs)
        visit("block", j, fish)
    visit("embed", None, {"embed": _embed_pass(w["embed"], tokens, cot, cs)})


def global_fisher(w, tokens, sh: Shape, cs: int, z_loss: float
                  ) -> Dict[str, jax.Array]:
    """The global Fisher I_D over a retain sample (rows of tokens)."""
    out: Dict[str, Any] = {}
    blocks: Dict[int, Dict[str, jax.Array]] = {}

    def visit(kind, j, fish):
        if kind == "block":
            blocks[j] = fish
        else:
            out.update(fish)

    fisher_sweep(w, tokens[:, :-1], tokens[:, 1:], sh, cs, z_loss, visit)
    for k in blocks[0]:
        out[k] = jnp.stack([blocks[j][k] for j in range(sh.L)])
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


def _scalars(sh: Shape, unl: Dict[str, Any]) -> np.ndarray:
    Lu = sh.L + 2
    S = profile(Lu, float(unl["b_r"]))
    out = np.empty((Lu, 2), np.float32)
    for l in range(1, Lu + 1):
        out[l - 1] = (unl["alpha"] * float(S[l - 1]),
                      unl["lam"] * float(S[l - 1]))
    return out


def stop_layer(sh: Shape, unl: Dict[str, Any]) -> int:
    """The paper layer a drain stops at.  With a negative target no
    checkpoint's forget accuracy (>= 0) can reach it: the sweep runs to the
    embedding, L = blocks + 2."""
    if unl["tau"] >= 0:
        raise NotImplementedError(
            "the reference drain implements full sweeps only (tau < 0)")
    return sh.L + 2


def drain(w, i_g, forget, sh: Shape, unl: Dict[str, Any],
          grad_rms: Optional[Dict[str, np.ndarray]] = None):
    """One drain of one forget set (rows of tokens) on weights ``w``.
    Returns the edited weights and the paper layer the sweep stopped at.
    ``grad_rms``, when given, receives each leaf's root-mean-square forget
    gradient per layer row (for the rule that leaves out leaves whose
    gradient is nought to rounding)."""
    Lu = stop_layer(sh, unl)
    sc = _scalars(sh, unl)
    new = dict(w)
    blocks = _layers(w)
    blocks_g = {k: i_g[k] for k in blocks}
    rows: Dict[str, List] = {k: [None] * sh.L for k in blocks}

    def note(rms, row=None):
        if grad_rms is None:
            return
        for k, v in rms.items():
            if row is None:
                grad_rms[k] = np.array([float(v)])
            else:
                grad_rms.setdefault(k, np.zeros(sh.L))[row] = float(v)

    def visit(kind, j, fish):
        if kind == "head":
            a, lam = sc[0]
            got, rms = _dampen_layer({k: w[k] for k in HEAD_LEAVES}, fish,
                                     {k: i_g[k] for k in HEAD_LEAVES}, a, lam)
            new.update(got)
            note(rms)
        elif kind == "embed":
            a, lam = sc[Lu - 1]
            got, rms = _dampen_layer({"embed": w["embed"]}, fish,
                                     {"embed": i_g["embed"]}, a, lam)
            new.update(got)
            note(rms)
        else:
            a, lam = sc[Lu - (j + 1) - 1]
            got, rms = _dampen_layer(_row(blocks, j), fish,
                                     _row(blocks_g, j), a, lam)
            for k, v in got.items():
                rows[k][j] = v
            note(rms, j)

    fisher_sweep(w, forget[:, :-1], forget[:, 1:], sh,
                 int(unl["fisher_chunk"]), 0.0, visit)
    for k, r in rows.items():
        new[k] = jnp.stack(r)
    return new, Lu
