"""Dense decoder-only transformers: the Llama / Qwen2 family.

Everything the benchmark knows of this architecture, found by the
configuration's ``model_type`` (``Registry.family``):

  * the weights in the benchmark's own layout (``shapes``, ``init``): a flat
    dict whose block leaves are stacked ``[L, ...]``, one segment;
  * the program's side (``lm_config``, ``program_tree``, ``neutral_tree``):
    the program's ``LMConfig`` and its parameter tree, holding the same
    arrays re-nested;
  * the plain float32 reference of the served model (``Shape``,
    ``segments``, ``head``, ``segment_logits``, ``init_cache``), written
    from the published descriptions at ``Precision.HIGHEST``: token
    embedding; per block ``x + Attn(RMSNorm(x))`` then
    ``x + SwiGLU(RMSNorm(x))`` with grouped-query attention, optional
    q/k/v bias and rotary embedding on the two halves of each head; final
    RMSNorm and an untied LM head;
  * the operations and bytes the served programs need, counted from shapes
    (``decode_step_flops``, ``decode_step_bytes``, ``forward_flops``,
    ``drain_flops``).  A multiply-add counts 2 operations.  Only what the
    algorithm needs is counted: a decode step is one forward of each
    occupied row's newest token against its causal context; a drain is one
    forward over the forget tokens plus the backward (parameter and input
    gradients, twice the forward) of every layer it sweeps.  Halt-checkpoint
    forwards, the per-chunk Fisher squares and padding rows are not
    counted, so a program that drops them can only come closer to, never
    past, the peak.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Iterable, List, Tuple

import jax
import jax.numpy as jnp

import reference as R
from reference import F32, HI

MODEL_TYPES = ("llama", "qwen2")

BLOCK_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                "w_gate", "w_up", "w_down")
HEAD_LEAVES = ("final_norm", "lm_head")


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    s = {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
         "ln1": (L, D), "ln2": (L, D), "wq": (L, D, H * dh),
         "wk": (L, D, KV * dh), "wv": (L, D, KV * dh), "wo": (L, H * dh, D),
         "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
    if cfg["attention_bias"]:
        s.update(bq=(L, H * dh), bk=(L, KV * dh), bv=(L, KV * dh))
    return s


def init(key, cfg):
    """Every leaf from one split of ``key``, in sorted leaf order."""
    dt = jnp.dtype(cfg["torch_dtype"])
    shp = shapes(cfg)
    keys = dict(zip(sorted(shp), jax.random.split(key, len(shp))))
    out = {}
    for name in sorted(shp):
        k, s = keys[name], shp[name]
        if name in ("ln1", "ln2", "final_norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, s, F32)
        elif name in ("bq", "bk", "bv"):
            w = 0.02 * jax.random.normal(k, s, F32)
        elif name == "embed":
            w = 0.02 * jax.random.normal(k, s, F32)
        else:   # fan-in scaled, as the published initialisers do
            w = jax.random.truncated_normal(k, -2.0, 2.0, s, F32) \
                / math.sqrt(s[-2])
        out[name] = w.astype(dt)
    return out


# ---------------------------------------------------------------------------
# The program's side
# ---------------------------------------------------------------------------
def lm_config(cfg: Dict[str, Any], LMConfig):
    """The program's ``LMConfig`` for a configuration file (HF key names)."""
    return LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        qkv_bias=cfg["attention_bias"], rope_theta=cfg["rope_theta"],
        prefix_len=cfg.get("num_image_token", 0),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"])


_BLOCK = {"ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
          "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
          "wo": ("mixer", "wo"), "bq": ("mixer", "bq"), "bk": ("mixer", "bk"),
          "bv": ("mixer", "bv"), "w_gate": ("ffn", "w_gate"),
          "w_up": ("ffn", "w_up"), "w_down": ("ffn", "w_down")}


def program_tree(w: Dict[str, Any]) -> Dict[str, Any]:
    """Benchmark layout (flat dict, block leaves stacked ``[L, ...]``) ->
    the program's tree (``period_stack`` of a one-block pattern)."""
    blk: Dict[str, Dict[str, Any]] = {}
    for name, (grp, leaf) in _BLOCK.items():
        if name in w:
            blk.setdefault(grp, {})[leaf] = w[name]
    return {"embed": {"w": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "period_stack": {"0": blk},
            "lm_head": {"w": w["lm_head"]}}


def neutral_tree(p: Dict[str, Any]) -> Dict[str, Any]:
    """The program's tree -> the benchmark layout (inverse of
    ``program_tree``)."""
    blk = p["period_stack"]["0"]
    w = {"embed": p["embed"]["w"], "final_norm": p["final_norm"]["scale"],
         "lm_head": p["lm_head"]["w"]}
    for name, (grp, leaf) in _BLOCK.items():
        if leaf in blk.get(grp, {}):
            w[name] = blk[grp][leaf]
    return w


# ---------------------------------------------------------------------------
# The reference model
# ---------------------------------------------------------------------------
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


def _qkv(lw, x, pos, sh: Shape, mm=R.mm):
    h = R.rmsnorm(x, lw["ln1"], sh.eps)
    q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
    if sh.bias:
        q = q + lw["bq"].astype(F32)
        k = k + lw["bk"].astype(F32)
        v = v + lw["bv"].astype(F32)
    lead = x.shape[:-1]
    q = R.rope(q.reshape(lead + (sh.H, sh.dh)), pos, sh.theta)
    k = R.rope(k.reshape(lead + (sh.KV, sh.dh)), pos, sh.theta)
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


def _ffn(lw, x, sh: Shape, mm=R.mm):
    h = R.rmsnorm(x, lw["ln2"], sh.eps)
    g, u = mm(h, lw["w_gate"]), mm(h, lw["w_up"])
    return x + mm(jax.nn.silu(g) * u, lw["w_down"])


def block(lw, x, sh: Shape):
    """One transformer block on x [..., S, D] at positions 0..S-1."""
    pos = jnp.arange(x.shape[-2])
    q, k, v = _qkv(lw, x, pos, sh)
    x = x + R.mm(_attend(q, k, v, sh), lw["wo"])
    return _ffn(lw, x, sh)


def head(hw, x, sh: Shape, mm=R.mm):
    return mm(R.rmsnorm(x, hw["final_norm"], sh.eps), hw["lm_head"])


def segments(sh: Shape) -> List[R.Segment]:
    """One segment: every block applies ``block``, its leaves stacked under
    their own names."""
    names = [k for k in BLOCK_LEAVES if sh.bias or k not in ("bq", "bk", "bv")]
    return [R.Segment(block, {k: k for k in names}, sh.L)]


def init_cache(sh: Shape, T: int):
    """The reference's key/value cache of one sequence of ``T`` positions,
    as ``segment_logits`` reads it: ``[L, 2, T, KV, dh]``."""
    return jnp.zeros((sh.L, 2, T, sh.KV, sh.dh), F32)


def _layers(w) -> Dict[str, jax.Array]:
    return {k: w[k] for k in BLOCK_LEAVES if k in w}


@partial(jax.jit, static_argnums=(2, 6))
def segment_logits(w, tokens, sh: Shape, kv, done, seg, quant=False):
    """Logits of one sequence whose positions were served by several weight
    versions.  ``tokens`` [T] are its inputs; ``kv`` (``init_cache``) holds
    the keys and values of the positions already computed (``done``) under
    earlier versions; this call computes every position with ``w`` but keeps
    the cached keys/values where ``done``, and returns the logits [T, V]
    (valid at the ``seg`` positions) and the cache with ``seg`` written.
    ``quant`` computes every product with a weight matrix in fp8, weights
    and activations alike (the control).
    """
    q8 = R.quant if quant else (lambda a: a)
    mm = R.mm8 if quant else R.mm
    emb = R.fp8(w["embed"], -1) if quant else w["embed"].astype(F32)
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


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------
def block_matmul_params(cfg: Dict[str, Any]) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict[str, Any], queries: int, keys: int) -> int:
    """Scores and weighted values of one layer: ``queries`` x ``keys``
    query-key pairs in all, each 2 dot products of ``head_dim``."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * queries * keys


def decode_step_flops(cfg: Dict[str, Any], contexts: Iterable[int]) -> int:
    """One decode step over rows whose new token attends to ``contexts``
    positions (itself included)."""
    L = cfg["num_hidden_layers"]
    per_row = 2 * (L * block_matmul_params(cfg) + head_params(cfg))
    total = 0
    for c in contexts:
        total += per_row + L * attention_flops(cfg, 1, c)
    return total


def decode_step_bytes(cfg: Dict[str, Any], contexts: Iterable[int],
                      weight_bytes: int = 2, cache_bytes: int = 2) -> int:
    """Bytes one decode step must read: every matrix once, and each row's
    cached keys and values of all its context positions in every layer."""
    L = cfg["num_hidden_layers"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    w = (L * block_matmul_params(cfg) + head_params(cfg)) * weight_bytes
    return w + sum(L * 2 * kv * c * cache_bytes for c in contexts)


def forward_flops(cfg: Dict[str, Any], n_seq: int, seq_len: int) -> int:
    """A full forward of ``n_seq`` sequences of ``seq_len`` tokens (causal
    attention: position i attends to i + 1 keys)."""
    L = cfg["num_hidden_layers"]
    tokens = n_seq * seq_len
    pairs = n_seq * seq_len * (seq_len + 1) // 2
    return (2 * tokens * (L * block_matmul_params(cfg) + head_params(cfg))
            + L * attention_flops(cfg, pairs, 1))


def drain_flops(cfg: Dict[str, Any], n_seq: int, seq_len: int,
                blocks_swept: int, head_swept: bool = True) -> int:
    """One drain: the forward over the forget set plus the backward of the
    head and of ``blocks_swept`` blocks (the embedding's backward is a
    scatter and counts nothing)."""
    tokens = n_seq * seq_len
    pairs = n_seq * seq_len * (seq_len + 1) // 2
    block_fwd = (2 * tokens * block_matmul_params(cfg)
                 + attention_flops(cfg, pairs, 1))
    head_fwd = 2 * tokens * head_params(cfg)
    back = 2 * (blocks_swept * block_fwd + (head_fwd if head_swept else 0))
    return forward_flops(cfg, n_seq, seq_len) + back
