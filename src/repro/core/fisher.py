"""Diagonal Fisher information estimation (Eq. 2).

``I_i = E[(d ln p(D|theta) / d theta_i)^2]`` estimated by accumulating squared
gradients of chunk log-likelihoods:

* ``chunk_size == 1`` reproduces the per-sample expectation of Eq. (2) exactly;
* larger chunks match the official SSD implementation (per-batch squared
  gradients), trading estimator variance for throughput.  The alpha-threshold
  comparison and the beta ratio are scale-invariant as long as I_Df and I_D
  use the same chunking, which we enforce at the FiCABU API level.

A batch whose length is not a multiple of ``chunk_size`` no longer errors:
the divisible head is chunked as usual and the partial TAIL is evaluated
exactly as one smaller chunk, then sample-weighted into the mean — padding
the tail with replicated samples would bias its chunk gradient, so the tail
gets its own (cached) program instead.  ``chunked`` itself, the low-level
reshape helper, still requires divisibility and now raises an actionable
``ValueError`` (never ``assert`` — user-facing validation rule of
repro.api).

Accumulation is always f32 (the FIMD IP's accumulator in the paper is a wide
fixed-point register for the same reason).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
Params = Any


def _square_tree(g):
    return jax.tree_util.tree_map(lambda x: (x.astype(F32)) ** 2, g)


def _add_trees(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _scale_tree(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def _batch_len(batch) -> int:
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        raise ValueError("Fisher estimation got an empty batch pytree — "
                         "pass (inputs, labels) arrays with a leading "
                         "sample dimension")
    return int(leaves[0].shape[0])


def _check_chunk_size(chunk_size) -> None:
    if not isinstance(chunk_size, int) or isinstance(chunk_size, bool) \
            or chunk_size < 1:
        raise ValueError(f"chunk_size must be an int >= 1, "
                         f"got {chunk_size!r}")


def chunked(batch, chunk_size: int):
    """Reshape every leaf [N, ...] -> [N//cs, cs, ...].

    N must be a multiple of ``chunk_size``; callers with a partial last
    chunk should use ``diag_fisher``, which splits the tail off and
    evaluates it exactly instead of reshaping."""
    _check_chunk_size(chunk_size)
    n = _batch_len(batch)
    if n % chunk_size != 0:
        raise ValueError(
            f"batch length {n} is not a multiple of chunk_size "
            f"{chunk_size}; pad the batch to a multiple, or call "
            f"diag_fisher / diag_fisher_streaming, which evaluate the "
            f"partial last chunk exactly at its own size")

    def r(x):
        return x.reshape(n // chunk_size, chunk_size, *x.shape[1:])
    return jax.tree_util.tree_map(r, batch)


def fisher_tree(loss_fn: Callable[[Params, Any], jax.Array], params: Params,
                batch: Any, chunk_size: int) -> Params:
    """Traceable diag-Fisher body (no jit): mean over chunks of squared
    chunk-gradients, with the partial tail (if any) evaluated exactly as one
    smaller chunk and sample-weighted into the mean.  Shapes are static at
    trace time, so the head/tail split is resolved before lowering — both
    ``diag_fisher`` and the streamed-refresh program
    (``repro.engine.fisher_stream``) lower this same body."""
    n = _batch_len(batch)
    if n < 1:
        # shapes are static even under jit, so this raises at TRACE time —
        # a zero-sample batch would otherwise mean(axis=0) over nothing and
        # silently return an all-NaN Fisher that poisons the installed I_D
        raise ValueError(
            "Fisher estimation needs at least one sample in the batch "
            "(leading dimension is 0 — check the retain split / refresh "
            "microbatch slicing)")
    head = (n // chunk_size) * chunk_size

    def mean_sq_over(chunks_batch, cs):
        chunks = chunked(chunks_batch, cs)
        n_chunks = _batch_len(chunks)

        def per_chunk(acc, c):
            sq = _square_tree(jax.grad(loss_fn)(params, c))
            return _add_trees(acc, sq), None

        # sequential sum in a scan carry: one f32 Fisher tree of extra
        # memory, where a stacked map would hold one per chunk
        zero = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32),
                                      params)
        total, _ = jax.lax.scan(per_chunk, zero, chunks)
        return jax.tree_util.tree_map(lambda x: x / n_chunks, total)

    if head == n:
        return mean_sq_over(batch, chunk_size)
    if head == 0:  # the whole batch is one partial chunk
        return mean_sq_over(batch, n)
    take = jax.tree_util.tree_map
    f_head = mean_sq_over(take(lambda x: x[:head], batch), chunk_size)
    f_tail = mean_sq_over(take(lambda x: x[head:], batch), n - head)
    w_h, w_t = head / n, (n - head) / n
    return jax.tree_util.tree_map(lambda a, b: w_h * a + w_t * b,
                                  f_head, f_tail)


@partial(jax.jit, static_argnums=(0, 3))
def _diag_fisher_jit(loss_fn, params, batch, chunk_size):
    return fisher_tree(loss_fn, params, batch, chunk_size)


def diag_fisher(loss_fn: Callable[[Params, Any], jax.Array], params: Params,
                batch: Any, chunk_size: int = 8) -> Params:
    """Diagonal Fisher of ``params`` on ``batch`` (leaves [N, ...]).

    ``loss_fn(params, chunk) -> scalar`` must be the mean NLL over the chunk.
    Returns a tree matching ``params`` with f32 leaves.  N need not divide
    ``chunk_size`` — see ``fisher_tree`` for the partial-tail handling."""
    _check_chunk_size(chunk_size)
    _batch_len(batch)  # empty-pytree check (n==0 raises in fisher_tree)
    return _diag_fisher_jit(loss_fn, params, batch, chunk_size)


def diag_fisher_streaming(loss_fn, params, batches: Iterable[Any],
                          chunk_size: int = 8) -> Params:
    """Global importance I_D over a dataset iterator (computed once after
    training and stored, per SSD).  Each batch contributes with equal
    weight (the per-batch Fisher mean), so k equal-length batches match
    ``diag_fisher`` over their concatenation up to f32 rounding."""
    total = None
    n = 0
    for b in batches:
        f = diag_fisher(loss_fn, params, b, chunk_size)
        total = f if total is None else _add_trees(total, f)
        n += 1
    if n == 0:
        raise ValueError(
            "diag_fisher_streaming got an empty dataset iterator — the "
            "global Fisher I_D needs at least one retain microbatch "
            "(check the retain split / data loader)")
    return _scale_tree(total, 1.0 / n)
