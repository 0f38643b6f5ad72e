"""Weights, forget domains and prompts, made by the benchmark from ``--seed``.

The weights are made on the device in one jitted call, in the type they are
served in, in the benchmark's own layout: a flat dict whose block leaves are
stacked ``[L, ...]`` (``system.program_tree`` re-nests the same arrays for
the program).  The reference reads these arrays and nothing the program
made; it regenerates them from the seed when it needs them again.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def prng_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


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


def _init(key, cfg):
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


def make_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """All weights, on the default device, from one jitted call."""
    return jax.jit(_init, static_argnums=1)(prng_key(seed), _Hashable(cfg))


class _Hashable(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def make_domains(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Synthetic forget domains: ``n_domains`` x ``forget_set`` sequences of
    ``forget_len + 1`` tokens, each domain drawn from its own slice of the
    vocabulary, rows shuffled.  Returns (tokens [N, S+1] int32, domain
    labels [N])."""
    n_dom, per, S = mix["domains"], mix["forget_set"], mix["forget_len"] + 1
    span = cfg["vocab_size"] // n_dom
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
    labels = np.repeat(np.arange(n_dom), per)
    tokens = labels[:, None] * span + rng.integers(0, span, (len(labels), S))
    order = rng.permutation(len(labels))
    return tokens[order].astype(np.int32), labels[order].astype(np.int32)


def make_prompts(cfg: Dict[str, Any], n: int, prompt_len: int, seed: int
                 ) -> np.ndarray:
    """``n`` prompts of ``prompt_len`` tokens, uniform over the vocabulary."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 2])
    return rng.integers(0, cfg["vocab_size"], (n, prompt_len)).astype(
        np.int32)
