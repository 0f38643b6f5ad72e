"""The one traffic generator: a mix file's parameters and a cell's rates in,
an open-loop schedule out.

Every seed gets the same set of arrivals in another order.  A stream's
arrivals (``generate_arrivals``, ``forget_arrivals``) are ``poisson``, the
default: the gaps between arrivals are the stratified quantiles of an
exponential distribution (a Poisson process with exactly
``round(rate * seconds)`` arrivals), shuffled by the seed and scaled so that
the last one falls inside the window; or ``even``: one period apart, at a
phase drawn from the seed.  What a
seed changes is the order of the gaps, which forget domain each request
names (Zipf over the domains), and which prompt each generate request
carries.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def arrivals(rate: float, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson process of ``rate`` per
    second conditioned on its count: a fixed set of gaps, shuffled."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    t = np.cumsum(gaps)
    return (t - gaps[0] * 0.5) * (seconds / (t[-1] + gaps.mean()))


def even(rate: float, seconds: float, rng: np.random.Generator
         ) -> np.ndarray:
    """Due times in [0, seconds) of ``round(rate * seconds)`` arrivals one
    period apart, the first at a phase drawn from the seed."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    return (np.arange(n) + rng.uniform()) * (seconds / n)


ARRIVALS = {"poisson": arrivals, "even": even}


def zipf(n: int, k: int, s: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws over ``k`` ranks with probability proportional to
    ``1 / rank**s`` (rank 1 most likely)."""
    p = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=p / p.sum())


def schedule(cell: Dict[str, Any], seed: int, seconds: float
             ) -> Dict[str, List[Tuple[float, int]]]:
    """``{"generate": [(due_s, prompt_index)], "forget": [(due_s,
    domain)]}``, each sorted by due time, for a cell merged over its mix
    (rates from the cell, domains and skew from the mix)."""
    g_t = ARRIVALS[cell.get("generate_arrivals", "poisson")](
        float(cell["generate_rate"]), seconds, _rng(seed, 10))
    gen = [(float(t), i) for i, t in enumerate(g_t)]
    f_rate = float(cell.get("forget_rate", 0.0))
    fgt: List[Tuple[float, int]] = []
    if f_rate > 0:
        rng = _rng(seed, 11)
        f_t = ARRIVALS[cell.get("forget_arrivals", "poisson")](
            f_rate, seconds, rng)
        doms = zipf(len(f_t), cell["domains"], float(cell["zipf_s"]), rng)
        fgt = [(float(t), int(d)) for t, d in zip(f_t, doms)]
    return {"generate": gen, "forget": fgt}
