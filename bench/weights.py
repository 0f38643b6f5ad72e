"""Weights, forget domains and prompts, made by the benchmark from ``--seed``.

The weights are made on the device in one jitted call, in the type they are
served in, in the benchmark's own layout, which the model's family defines
(``bench/families/<family>.py``: ``shapes``, ``init``; its ``program_tree``
re-nests the same arrays for the program).  The reference reads these
arrays and nothing the program made; it regenerates them from the seed when
it needs them again.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np


def prng_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def make_weights(fam, cfg: Dict[str, Any], seed: int
                 ) -> Dict[str, jax.Array]:
    """All weights of the configuration's family ``fam``, on the default
    device, from one jitted call."""
    return jax.jit(fam.init, static_argnums=1)(prng_key(seed),
                                               _Hashable(cfg))


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
