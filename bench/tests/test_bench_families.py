"""The dense family (``bench/families/dense.py``) makes, computes and counts
what the benchmark did before the family seam: its weight shapes, its
weights, the reference's logits, global Fisher and drain, and the counts of
the served programs, pinned to values read before the code moved."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny  # noqa: E402
import reference as R  # noqa: E402
import weights as Wt  # noqa: E402
from registry import Registry  # noqa: E402

SEED = 2**33 + 5
BIG = json.load(open(os.path.join(BENCH, "configs", "internvl2-1b-lm.json")))

SHAPES = {
    "tiny": {
        "embed": (256, 64), "final_norm": (64,), "lm_head": (64, 256),
        "ln1": (2, 64), "ln2": (2, 64), "wq": (2, 64, 64),
        "wk": (2, 64, 32), "wv": (2, 64, 32), "wo": (2, 64, 64),
        "w_gate": (2, 64, 160), "w_up": (2, 64, 160),
        "w_down": (2, 160, 64), "bq": (2, 64), "bk": (2, 32),
        "bv": (2, 32)},
    "internvl2-1b-lm": {
        "embed": (151655, 896), "final_norm": (896,),
        "lm_head": (896, 151655), "ln1": (24, 896), "ln2": (24, 896),
        "wq": (24, 896, 896), "wk": (24, 896, 128), "wv": (24, 896, 128),
        "wo": (24, 896, 896), "w_gate": (24, 896, 4864),
        "w_up": (24, 896, 4864), "w_down": (24, 4864, 896),
        "bq": (24, 896), "bk": (24, 128), "bv": (24, 128)},
}


def digest(tree) -> str:
    """sha256 of every leaf's name, dtype, shape and bytes, in key order."""
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        for part in (k, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.fixture(scope="module")
def dense():
    cfg = dict(tiny.TINY_CONFIG)
    fam = Registry().family(cfg)
    return fam, cfg, Wt.make_weights(fam, cfg, SEED)


@pytest.fixture(scope="module")
def drained(dense):
    import jax.numpy as jnp
    fam, cfg, w = dense
    sh = fam.Shape(cfg)
    tokens, labels = Wt.make_domains(cfg, tiny.TINY_MIX, SEED)
    i_g = R.global_fisher(fam, w, jnp.asarray(tokens[:32]), sh, 4, 1e-4)
    rms = {}
    new, stop = R.drain(fam, w, i_g, jnp.asarray(tokens[labels == 1][:8]),
                        sh, dict(tiny.UNLEARN, tau=-1.0), rms)
    return i_g, new, stop, rms


def test_configs_find_the_dense_family():
    reg = Registry()
    for name in ("internvl2-1b-lm", "yi-6b-s2"):
        fam = reg.family(reg.config(name))
        assert fam.MODEL_TYPES == ("llama", "qwen2")
        assert fam is reg.family(tiny.TINY_CONFIG)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_shapes_are_pinned(name):
    cfg = tiny.TINY_CONFIG if name == "tiny" else BIG
    assert Registry().family(cfg).shapes(cfg) == SHAPES[name]


def test_weights_are_pinned(dense):
    assert digest(dense[2]) == "eccb6a463553fe9b"


def test_reference_logits_are_pinned(dense, drained):
    """Two versions over one sequence (positions 0..9, then 10..23 under
    the drained tree, reading the first version's cache), and the fp8
    control's logits."""
    import jax.numpy as jnp
    fam, cfg, w = dense
    sh = fam.Shape(cfg)
    T = 24
    toks = jnp.asarray(Wt.make_prompts(cfg, 1, T + 1, 7)[0][:T])
    pos = np.arange(T)
    kv = fam.init_cache(sh, T)
    l1, kv1 = fam.segment_logits(w, toks, sh, kv, jnp.asarray(pos < 0),
                                 jnp.asarray(pos < 10))
    l2, kv2 = fam.segment_logits(drained[1], toks, sh, kv1,
                                 jnp.asarray(pos < 10),
                                 jnp.asarray(pos >= 10))
    assert digest({"l1": l1, "kv1": kv1, "l2": l2, "kv2": kv2}) \
        == "69024886273a4d68"
    l3, kv3 = fam.segment_logits(w, toks, sh, kv, jnp.asarray(pos < 0),
                                 jnp.asarray(pos < 10), True)
    assert digest({"l3": l3, "kv3": kv3}) == "c361939f9fc60615"


def test_global_fisher_is_pinned(drained):
    assert digest(drained[0]) == "b1f8a1e82ef007b0"


def test_drain_is_pinned(drained):
    _, new, stop, rms = drained
    assert stop == 4
    assert digest(new) == "cecd6e70bc04539e"
    assert digest(rms) == "240bcef3e6c0f951"


def test_counts_are_pinned():
    fam = Registry().family(BIG)
    ctx = [513, 600, 640]
    assert fam.decode_step_flops(BIG, ctx) == 3113042688
    assert fam.decode_step_bytes(BIG, ctx) == 1008959744
    assert fam.forward_flops(BIG, 8, 128) == 1016798117888
    assert fam.drain_flops(BIG, 8, 128, 24) == 3050394353664
    assert fam.drain_flops(BIG, 8, 128, 3, head_swept=False) \
        == 1201425612800


ARCH_KEYS = ("num_key_value_heads", "intermediate_size", "head_dim",
             "attention_bias", "rope_theta")


@pytest.mark.parametrize("module", sorted(
    f for f in os.listdir(BENCH) if f.endswith(".py")))
def test_only_families_read_architecture_keys(module):
    with open(os.path.join(BENCH, module)) as f:
        text = f.read()
    assert [k for k in ARCH_KEYS if k in text] == []
