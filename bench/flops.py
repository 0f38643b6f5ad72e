"""Operations and bytes the served programs need, counted from shapes.

A multiply-add counts 2 operations.  Only what the algorithm needs is
counted: a decode step is one forward of each occupied row's newest token
against its causal context; a drain is one forward over the forget tokens
plus the backward (parameter and input gradients, twice the forward) of
every layer it sweeps.  Halt-checkpoint forwards, the per-chunk Fisher
squares and padding rows are not counted, so a program that drops them can
only come closer to, never past, the peak.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable


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
