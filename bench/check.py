"""The comparison that decides ``correct``: what the timed path served and
what it published, against the plain reference (``reference.py``).

It runs once the window has closed, the peak memory has been read and the
program's state is freed.  Version 0 is the seed's weights, which the window
serves until its first drain publishes; version 1 is the tree that first
publication put in the decode step's hands.  The reference makes version 0
from the seed and version 1 by its own drain of the same forget set.

  * ``decode_gap``     over a sample of the window's finished requests that
                       were served, in part or whole, by versions 0 and 1:
                       the widest gap by which a served token lies below
                       the reference's best logit at its position, each
                       position under the version that served it (prefill's
                       token and every decode step's);
  * ``edit_mismatch``  the window's first published tree, as the decode
                       step reads it, against the reference drain: the
                       elements edited by exactly one of the two, as a
                       share of those the reference edited.  Leaves whose
                       forget gradient is nought to rounding in the
                       reference (RMS under a thousandth of the median
                       leaf's) are left out by that rule, not by name;
  * ``drain_mismatch`` window drains whose domain or halting layer differ
                       from the reference's (exact: limit 0).

Why versions 0 and 1 only: every drain starts from the previous one's
output, and a parameter near the selection threshold flips with the
rounding of its Fisher, so two correct implementations at different
precisions drift apart drain by drain; the first drain, from identical
weights, is the comparison that stays steady.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as Wt

GRAD_FLOOR = 1e-3


_pack = jax.jit(lambda a, b: jnp.packbits((a != b).reshape(-1)))


def changed_bits(live: Dict[str, Any], base: Dict[str, Any]
                 ) -> Dict[str, jax.Array]:
    """Per leaf, which elements differ from ``base``, packed eight to a
    byte on the device (``jnp.packbits`` of the flattened leaf); it is
    dispatched without a wait, and ``np.asarray`` reads it later.  Set-up
    calls it once on the served tree, so the window compiles nothing."""
    return {k: _pack(live[k], base[k]) for k in live}


def _mismatch(prog_bits: Dict[str, np.ndarray], ref: Dict[str, Any],
              base: Dict[str, Any], counted: Dict[str, np.ndarray]) -> float:
    """Elements edited by exactly one side over those the reference edited,
    summed over the counted leaf rows (``counted[k]``: one flag per row of
    a leaf stacked over blocks, one for any other leaf)."""
    xor_n = ref_n = 0
    for k, a in ref.items():
        diff = a != base[k]
        n = int(np.prod(a.shape))
        prog = jnp.unpackbits(jnp.asarray(prog_bits[k]))[:n].reshape(a.shape)
        # a stack of one block sums to the same one row either way
        axes = tuple(range(1, a.ndim)) if len(counted[k]) > 1 else None
        x = np.atleast_1d(np.asarray(jnp.sum(diff != prog.astype(bool),
                                             axis=axes)))
        r = np.atleast_1d(np.asarray(jnp.sum(diff, axis=axes)))
        keep = counted[k]
        xor_n += int(x[keep].sum())
        ref_n += int(r[keep].sum())
    return xor_n / max(ref_n, 1)


def _gaps(fam, trees: List[Dict[str, Any]], s: Dict[str, np.ndarray], sh,
          P: int, quant: bool) -> float:
    """The widest gap over one sampled request's positions served by the
    versions in ``trees``; under ``quant``, the gap under ``trees`` of the
    token that the fp8 model puts first."""
    T = len(s["versions"])
    pos = np.arange(T)
    toks = jnp.asarray(s["tokens"][:T])
    nxt = jnp.asarray(s["tokens"][1:T + 1])
    kv = fam.init_cache(sh, T)
    kv_low = kv
    gap = 0.0
    for v, w in enumerate(trees):
        seg = s["versions"] == v
        if not seg.any():
            continue
        done = jnp.asarray(s["versions"] < v)
        mask = jnp.asarray(seg & (pos >= P - 1))
        logits, kv = fam.segment_logits(w, toks, sh, kv, done,
                                        jnp.asarray(seg))
        if quant:
            low, kv_low = fam.segment_logits(w, toks, sh, kv_low, done,
                                             jnp.asarray(seg), True)
            g = R.control_gaps(logits, low, mask)
            del low
        else:
            g = R.token_gaps(logits, nxt, mask)
        gap = max(gap, float(jnp.max(g)))
        del logits
    return gap


def compare(fam, cfg: Dict[str, Any], cell: Dict[str, Any], seed: int,
            tokens: np.ndarray, labels: np.ndarray,
            samples: List[Dict[str, np.ndarray]],
            drains: List[Dict[str, Any]],
            first_bits: Optional[Dict[str, np.ndarray]],
            control: bool = False) -> Dict[str, Any]:
    """``fam``: the configuration's family (``bench/families/``);
    ``samples``: served sequences with the version that served each
    position (0, 1, or later, which is not compared); ``drains``:
    ``{"domain", "prog_domain", "prog_stop"}`` of the window's drains in
    order; ``first_bits``: the elements the first publication changed in
    the decode step's tree (``changed_bits``), None if the window published
    nothing or the capture failed.  Returns ``decode_gap`` (and
    ``control_gap`` under ``control``) and, where the cell drains,
    ``edit_mismatch`` and ``drain_mismatch``."""
    sh = fam.Shape(cfg)
    unl = dict(cell["unlearn"], tau=float(cell["tau"]))
    P = cell["prompt_len"]
    w = Wt.make_weights(fam, cfg, seed)
    trees = [w]
    out: Dict[str, Any] = {}
    if drains:
        out["drain_mismatch"] = sum(
            1 for d in drains
            if d["prog_domain"] != d["domain"]
            or d["prog_stop"] != R.stop_layer(R.n_blocks(fam, sh), unl))
        retain = jnp.asarray(tokens[:int(unl["retain_sample"])])
        i_g = R.global_fisher(fam, w, retain, sh, int(unl["fisher_chunk"]),
                              float(unl["z_loss_global"]))
        rows = tokens[labels == drains[0]["domain"]][:cell["forget_set"]]
        grad_rms: Dict[str, np.ndarray] = {}
        new, _ = R.drain(fam, w, i_g, jnp.asarray(rows), sh, unl, grad_rms)
        del i_g
        trees.append(new)
        if first_bits is None:
            out["edit_mismatch"] = 1.0
        else:
            med = float(np.median(np.concatenate(list(grad_rms.values()))))
            counted = {k: r >= GRAD_FLOOR * med for k, r in grad_rms.items()}
            out["edit_mismatch"] = _mismatch(first_bits, new, w, counted)
    out["decode_gap"] = max([_gaps(fam, trees, s, sh, P, False)
                             for s in samples] or [0.0])
    out["control_gap"] = (max([_gaps(fam, trees, s, sh, P, True)
                               for s in samples] or [0.0])
                          if control else None)
    return out
