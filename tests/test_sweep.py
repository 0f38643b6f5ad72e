"""Scanned whole-sweep megaprogram (repro.engine.sweep) tests:

  * the scanned sweep is BIT-exact vs the layerwise drive loop (the oracle)
    on LM (mixed block kinds + tied embeddings, the gemma3 shape) and ViT —
    edited params, ``stopped_at_l``, per-layer selection counts, the
    checkpoint accuracy trace and MAC accounting all identical;
  * device-side halting: a set that reaches tau mid-sweep stops editing
    more frontal layers (masked continuation), and the coalesced vmapped
    drain preserves per-set halting masks and split-edit semantics;
  * automatic fallbacks: heterogeneous stacks (ResNet) and ragged drain
    groups route to the layerwise driver;
  * program-cache lifecycle: ONE sweep compile, then zero warm retraces
    (TRACE_LOG pin) across repeats, hyperparameter changes, and coalesced
    re-drains;
  * per-layer parameter counts: from shapes alone (every adapter family),
    memoised per tree shape in the program cache;
  * the API plumbing: ``ExecSpec.sweep_mode`` validation / JSON round trip
    / ``to_config`` lowering, and ``dist.sharding.stacked_param_pspecs``
    for the stacked [L, ...] trees.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adapters, cau, fisher
from repro.data import synthetic as syn
from repro.engine import TRACE_LOG, UnlearnSession, plan_scanned_sweep
from repro.models import lm as LM
from repro.models import vision as V


@pytest.fixture()
def trace_log():
    TRACE_LOG.clear()
    yield TRACE_LOG
    TRACE_LOG.clear()


def _scanned(cfg: cau.UnlearnConfig) -> cau.UnlearnConfig:
    return dataclasses.replace(cfg, sweep_mode="scanned")


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_stats_equal(sa, sb):
    for key in ("stopped_at_l", "selected_per_layer", "checkpoints_hit",
                "forget_acc_trace", "macs", "macs_ssd", "macs_vs_ssd_pct"):
        assert sa[key] == sb[key], (key, sa[key], sb[key])


@pytest.fixture(scope="module")
def lm_setting():
    """A gemma3-shaped stack: mixed local/global block pattern (two layer
    KINDS, so the scan must segment, not assume one program body) and tied
    embeddings (the head reads the embedding as context)."""
    cfg_m = LM.LMConfig(name="t-sweep", n_layers=4, d_model=32, n_heads=4,
                        n_kv_heads=2, d_ff=64, vocab=64,
                        block_pattern=("local", "attn"), window=8,
                        tie_embeddings=True)
    dcfg = syn.LMDataConfig(vocab=64, n_domains=4, seq_len=16,
                            n_per_domain=8, seed=1)
    toks, doms = syn.make_lm_domains(dcfg)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg_m)
    loss_fn = lambda p, b: LM.lm_loss(p, cfg_m, b[0], b[1], aux_weight=0.0)
    i_d = fisher.diag_fisher(loss_fn, params, (toks[:, :-1], toks[:, 1:]),
                             chunk_size=4)
    adapter = adapters.lm_adapter(cfg_m, 16)
    logits, _ = adapter.forward_collect(params, toks[:8, :-1])
    return {"cfg": cfg_m, "toks": toks, "doms": doms, "params": params,
            "i_d": i_d, "adapter": adapter,
            "hard_labels": jnp.argmax(logits, -1)}  # model argmax: acc ~1.0


# ---------------------------------------------------------------------------
# bit-exactness vs the layerwise oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tau,balanced", [(-1.0, True), (0.2, True),
                                          (0.5, False)])
def test_scanned_matches_layerwise_lm(lm_setting, tau, balanced):
    m = lm_setting
    fb = m["toks"][:8]
    labels = m["hard_labels"] if tau == 0.5 else fb[:, 1:]
    cfg = cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=tau, checkpoint_every=1,
                            balanced=balanced, chunk_size=4)
    p_lw, s_lw = UnlearnSession(m["adapter"], m["i_d"]).forget(
        m["params"], fb[:, :-1], labels, cfg)
    p_sc, s_sc = UnlearnSession(m["adapter"], m["i_d"]).forget(
        m["params"], fb[:, :-1], labels, _scanned(cfg))
    assert s_sc["engine"]["sweep_mode"] == "scanned"
    assert s_lw["engine"]["sweep_mode"] == "layerwise"
    _assert_trees_equal(p_lw, p_sc)
    _assert_stats_equal(s_lw, s_sc)


def test_scanned_matches_layerwise_vit(key):
    cfg_m = V.ViTConfig(name="vit-t", n_layers=4, d_model=32, n_heads=2,
                        d_ff=64, n_classes=6, img_size=16, patch=4)
    params = V.init_vit(key, cfg_m)
    dcfg = syn.ClsDataConfig(n_classes=6, n_per_class=8, img_size=16, seed=0)
    x, y = syn.make_classification(dcfg)
    loss_fn = lambda p, b: V.cls_loss(V.vit_forward(p, cfg_m, b[0]), b[1])
    i_d = fisher.diag_fisher(loss_fn, params, (x[:16], y[:16]), chunk_size=8)
    adapter = adapters.vit_adapter(cfg_m)
    cfg = cau.UnlearnConfig(alpha=5.0, lam=1.0, tau=-1.0, checkpoint_every=2,
                            balanced=True, chunk_size=8)
    p_lw, s_lw = UnlearnSession(adapter, i_d).forget(params, x[:16], y[:16],
                                                     cfg)
    p_sc, s_sc = UnlearnSession(adapter, i_d).forget(params, x[:16], y[:16],
                                                     _scanned(cfg))
    assert s_sc["engine"]["sweep_mode"] == "scanned"
    _assert_trees_equal(p_lw, p_sc)
    _assert_stats_equal(s_lw, s_sc)


def test_scanned_bounded_sweep_matches(lm_setting):
    """cfg.max_layers bounds the scanned sweep exactly like the layerwise
    loop (the scan range and the front step are both gated)."""
    m = lm_setting
    fb = m["toks"][:8]
    for ml in (1, 2, 4):
        cfg = cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=-1.0,
                                checkpoint_every=2, chunk_size=4,
                                max_layers=ml)
        p_lw, s_lw = UnlearnSession(m["adapter"], m["i_d"]).forget(
            m["params"], fb[:, :-1], fb[:, 1:], cfg)
        p_sc, s_sc = UnlearnSession(m["adapter"], m["i_d"]).forget(
            m["params"], fb[:, :-1], fb[:, 1:], _scanned(cfg))
        assert s_sc["engine"]["sweep_mode"] == "scanned"
        _assert_trees_equal(p_lw, p_sc)
        _assert_stats_equal(s_lw, s_sc)


# ---------------------------------------------------------------------------
# device-side halting + coalesced (vmapped) drains
# ---------------------------------------------------------------------------
def test_scanned_coalesced_matches_and_halts(lm_setting):
    """One coalesced scanned drain == the layerwise coalesced oracle: an
    easy set (random labels) halts at the first checkpoint and stops
    editing frontal layers, the hard set (model argmax labels) sweeps on —
    per-set stats and the composed edits bit-match."""
    m = lm_setting
    toks = m["toks"]
    setH = (toks[:8, :-1], m["hard_labels"])
    labB = jax.random.randint(jax.random.PRNGKey(7), m["hard_labels"].shape,
                              0, 64)
    setE = (toks[8:16, :-1], labB)
    cfg = cau.UnlearnConfig(alpha=32.0, lam=0.9, tau=0.5, checkpoint_every=1,
                            balanced=False, chunk_size=4)
    p_lw, st_lw, g_lw = UnlearnSession(m["adapter"], m["i_d"]).forget_many(
        m["params"], [setH, setE], cfg)
    p_sc, st_sc, g_sc = UnlearnSession(m["adapter"], m["i_d"]).forget_many(
        m["params"], [setH, setE], _scanned(cfg))
    assert g_sc["engine"]["sweep_mode"] == "scanned"
    assert g_sc["engine"]["sweep_launches"] == 1
    _assert_trees_equal(p_lw, p_sc)
    for a, b in zip(st_lw, st_sc):
        _assert_stats_equal(a, b)
    # the halting mask semantics: the easy set stopped at l=1 and edited
    # ONLY the head; the hard set swept the full stack
    L = m["adapter"].n_layers
    assert g_sc["stopped_at_l"] == [L, 1]
    assert list(st_sc[1]["selected_per_layer"]) == [1]
    assert st_sc[1]["macs"] < st_sc[0]["macs"]


def test_scanned_reference_snapshot_matches(lm_setting):
    """``forget_many(reference=snapshot)`` after an earlier edit: vjp and
    Fisher stay pinned to the snapshot, but halt checkpoints must evaluate
    against the EDIT tree — under tied embeddings the two trees carry
    different embeddings, and the scanned program must split its head
    contexts exactly like the layerwise oracle does."""
    m = lm_setting
    toks = m["toks"]
    setA = (toks[:8, :-1], toks[:8, 1:])
    setB = (toks[8:16, :-1], toks[8:16, 1:])
    cfg = cau.UnlearnConfig(alpha=4.0, lam=0.5, tau=0.02, checkpoint_every=1,
                            balanced=True, chunk_size=4)
    sess = UnlearnSession(m["adapter"], m["i_d"])
    # first drain: full sweep (no early stop) so the embedding IS edited
    p1, _, _ = sess.forget_many(
        m["params"], [setA], dataclasses.replace(cfg, tau=-1.0))
    # the first drain must have actually edited the embedding, else the two
    # head contexts coincide and this test pins nothing
    assert not bool(jnp.array_equal(m["params"]["embed"]["w"],
                                    p1["embed"]["w"]))
    p_lw, st_lw, _ = sess.forget_many(p1, [setB], cfg,
                                      reference=m["params"])
    p_sc, st_sc, g_sc = UnlearnSession(m["adapter"], m["i_d"]).forget_many(
        p1, [setB], _scanned(cfg), reference=m["params"])
    assert g_sc["engine"]["sweep_mode"] == "scanned"
    _assert_trees_equal(p_lw, p_sc)
    _assert_stats_equal(st_lw[0], st_sc[0])


def test_scanned_single_set_group_matches_forget(lm_setting):
    """forget_many([A]) through the scanned program == scanned forget(A) ==
    layerwise forget(A), stats included."""
    m = lm_setting
    fb = m["toks"][:8]
    cfg = _scanned(cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=0.2,
                                     checkpoint_every=2, balanced=True,
                                     chunk_size=4))
    p_g, st_g, _ = UnlearnSession(m["adapter"], m["i_d"]).forget_many(
        m["params"], [(fb[:, :-1], fb[:, 1:])], cfg)
    p_f, st_f = UnlearnSession(m["adapter"], m["i_d"]).forget(
        m["params"], fb[:, :-1], fb[:, 1:], cfg)
    _assert_trees_equal(p_g, p_f)
    _assert_stats_equal(st_g[0], st_f)


# ---------------------------------------------------------------------------
# fallbacks
# ---------------------------------------------------------------------------
def test_resnet_falls_back_to_layerwise(trained_resnet):
    """ResNet's per-stage activation shapes are heterogeneous: requesting
    "scanned" silently (and correctly) runs the layerwise driver."""
    m = trained_resnet
    splits = syn.split_forget_retain(m["x"], m["y"], forget_class=2)
    fx, fy = splits["forget"]
    i_d = fisher.diag_fisher_streaming(m["loss_fn"], m["params"],
                                       [(m["x"][:32], m["y"][:32])],
                                       chunk_size=8)
    adapter = adapters.resnet_adapter(m["cfg"])
    assert plan_scanned_sweep(adapter, m["params"], fx[:32]) is None
    cfg = _scanned(cau.UnlearnConfig(alpha=10.0, lam=1.0, tau=1 / 6 + 0.03,
                                     checkpoint_every=2, balanced=True,
                                     chunk_size=8))
    p_sc, s_sc = UnlearnSession(adapter, i_d).forget(
        m["params"], fx[:32], fy[:32], cfg)
    assert s_sc["engine"]["sweep_mode"] == "layerwise"
    p_lw, s_lw = UnlearnSession(adapter, i_d).forget(
        m["params"], fx[:32], fy[:32], dataclasses.replace(
            cfg, sweep_mode="layerwise"))
    _assert_trees_equal(p_lw, p_sc)
    _assert_stats_equal(s_lw, s_sc)


def test_ragged_group_falls_back(lm_setting):
    """A drain group whose forget sets differ in batch shape cannot stack:
    the scanned request routes through the layerwise coalesced sweep."""
    m = lm_setting
    toks = m["toks"]
    cfg = _scanned(cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=-1.0,
                                     checkpoint_every=2, chunk_size=4))
    sets = [(toks[:8, :-1], toks[:8, 1:]), (toks[8:12, :-1], toks[8:12, 1:])]
    _, _, gs = UnlearnSession(m["adapter"], m["i_d"]).forget_many(
        m["params"], sets, cfg)
    assert gs["engine"]["sweep_mode"] == "layerwise"


# ---------------------------------------------------------------------------
# program-cache lifecycle: one compile, zero warm retraces
# ---------------------------------------------------------------------------
def test_sweep_family_zero_warm_retraces(lm_setting, trace_log):
    m = lm_setting
    fb = m["toks"][:8]
    cfg = _scanned(cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=-1.0,
                                     checkpoint_every=2, balanced=True,
                                     chunk_size=4))
    sess = UnlearnSession(m["adapter"], m["i_d"])
    _, s1 = sess.forget(m["params"], fb[:, :-1], fb[:, 1:], cfg)
    assert s1["engine"]["compiles"] == 1          # ONE program, whole sweep
    assert sess.stats["sweep_compiles"] == 1
    assert sess.stats["sweep_launches"] == 1

    trace_log.clear()
    _, s2 = sess.forget(m["params"], fb[:, :-1], fb[:, 1:], cfg)
    assert s2["engine"]["compiles"] == 0
    assert s2["engine"]["cache_hits"] == 1
    assert len(trace_log) == 0, f"unexpected retraces: {trace_log}"

    # (alpha, lam, tau) and the BD profile are traced operands: changing
    # them replays the same executable
    cfg2 = _scanned(cau.UnlearnConfig(alpha=9.0, lam=0.7, tau=0.4,
                                      checkpoint_every=2, balanced=True,
                                      b_r=5.0, chunk_size=4))
    _, s3 = sess.forget(m["params"], fb[:, :-1], fb[:, 1:], cfg2)
    assert s3["engine"]["compiles"] == 0
    assert len(trace_log) == 0, f"unexpected retraces: {trace_log}"
    assert sess.stats["sweep_launches"] == 3

    # a refreshed Fisher (same structure, new values) replays it too
    sess.fisher_global = jax.tree_util.tree_map(lambda x: x * 1.5,
                                                m["i_d"])
    _, s4 = sess.forget(m["params"], fb[:, :-1], fb[:, 1:], cfg)
    assert s4["engine"]["compiles"] == 0
    assert len(trace_log) == 0, f"unexpected retraces: {trace_log}"


def test_coalesced_second_drain_zero_retraces(lm_setting, trace_log):
    m = lm_setting
    toks, doms = m["toks"], m["doms"]
    sets = []
    for d in (1, 2):
        fb = toks[doms == d][:8]
        sets.append((fb[:, :-1], fb[:, 1:]))
    cfg = _scanned(cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=-1.0,
                                     checkpoint_every=2, balanced=True,
                                     chunk_size=4))
    sess = UnlearnSession(m["adapter"], m["i_d"])
    _, _, g1 = sess.forget_many(m["params"], sets, cfg)
    assert g1["engine"]["compiles"] == 1
    trace_log.clear()
    _, _, g2 = sess.forget_many(m["params"], sets, cfg)
    assert g2["engine"]["compiles"] == 0
    assert g2["engine"]["cache_hits"] == 1
    assert g2["engine"]["sweep_launches"] == 1
    assert len(trace_log) == 0, f"unexpected retraces: {trace_log}"


# ---------------------------------------------------------------------------
# per-layer parameter counts: from shapes alone, memoised per tree shape
# ---------------------------------------------------------------------------
def _eager_param_counts(adapter, params):
    """The counts as the engine once took them: slice each layer out of the
    concrete tree on the device and sum the leaf sizes."""
    return [sum(x.size for x in jax.tree_util.tree_leaves(
        adapter.get_layer(params, j))) for j in range(adapter.n_layers)]


def _family_lm(key):
    # 5 blocks over a 2-kind pattern: two stacked periods plus a tail block
    cfg_m = LM.LMConfig(name="t-cnt", n_layers=5, d_model=32, n_heads=4,
                        n_kv_heads=2, d_ff=64, vocab=64,
                        block_pattern=("local", "attn"), window=8,
                        tie_embeddings=True)
    return adapters.lm_adapter(cfg_m, 16), LM.init_lm(key, cfg_m)


def _family_moe_lm(key):
    cfg_m = LM.LMConfig(name="moe-cnt", n_layers=2, d_model=32, n_heads=4,
                        n_kv_heads=2, d_ff=64, vocab=64,
                        moe=LM.MoESpec(num_experts=4, top_k=2))
    return adapters.lm_adapter(cfg_m, 16), LM.init_lm(key, cfg_m)


def _family_resnet(key):
    cfg_m = V.ResNetConfig(width=8, n_classes=6, img_size=16)
    return adapters.resnet_adapter(cfg_m), V.init_resnet(key, cfg_m)


def _family_vit(key):
    cfg_m = V.ViTConfig(name="vit-cnt", n_layers=3, d_model=32, n_heads=2,
                        d_ff=64, n_classes=6, img_size=16, patch=4)
    return adapters.vit_adapter(cfg_m), V.init_vit(key, cfg_m)


def _family_encdec(key):
    from repro.models import encdec as ED
    cfg_m = ED.EncDecConfig(name="ed-cnt", n_enc_layers=1, n_dec_layers=2,
                            d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                            vocab=64, n_frames=8)
    adapter = adapters.encdec_adapter(cfg_m, 8, jnp.zeros((8, 8, 32)))
    return adapter, ED.init_encdec(key, cfg_m)


@pytest.mark.parametrize("family", [_family_lm, _family_moe_lm,
                                    _family_resnet, _family_vit,
                                    _family_encdec],
                         ids=["lm", "moe_lm", "resnet", "vit", "encdec"])
def test_layer_param_counts_from_shapes(key, family):
    """``_layer_param_counts`` on a tree of ShapeDtypeStructs — which
    cannot dispatch a device op — equals the eager per-layer slicing count
    on the concrete tree, for every adapter family."""
    adapter, params = family(key)
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    counts = cau._layer_param_counts(adapter, abstract)
    assert counts == _eager_param_counts(adapter, params)
    assert all(type(c) is int and c > 0 for c in counts)
    assert cau._layer_param_counts(adapter, params) == counts


def test_param_counts_memoised_per_shape(lm_setting):
    """Two scanned drains on one session count once and hit once; a second
    session sharing the program cache hits too. The memoised counts give
    the MAC statistics the legacy oracle computes from scratch."""
    m = lm_setting
    toks = m["toks"]
    fb = toks[:8]
    cfg = _scanned(cau.UnlearnConfig(alpha=6.0, lam=0.5, tau=-1.0,
                                     checkpoint_every=2, balanced=True,
                                     chunk_size=4))
    sess = UnlearnSession(m["adapter"], m["i_d"])
    sess.forget_many(m["params"], [(toks[8:16, :-1], toks[8:16, 1:])], cfg)
    assert (sess.stats["param_count_builds"],
            sess.stats["param_count_hits"]) == (1, 0)
    _, st, g = sess.forget_many(m["params"], [(fb[:, :-1], fb[:, 1:])], cfg)
    assert g["engine"]["sweep_mode"] == "scanned"
    assert (sess.stats["param_count_builds"],
            sess.stats["param_count_hits"]) == (1, 1)

    _, s_legacy = cau.context_adaptive_unlearn_legacy(
        m["adapter"], m["params"], m["i_d"], fb[:, :-1], fb[:, 1:], cfg)
    for k in ("macs", "macs_ssd", "macs_vs_ssd_pct"):
        assert st[0][k] == s_legacy[k], (k, st[0][k], s_legacy[k])

    tenant = UnlearnSession(m["adapter"], m["i_d"], programs=sess.programs)
    _, st_t, _ = tenant.forget_many(m["params"], [(fb[:, :-1], fb[:, 1:])],
                                    cfg)
    assert (tenant.stats["param_count_builds"],
            tenant.stats["param_count_hits"]) == (0, 1)
    assert st_t[0]["macs"] == s_legacy["macs"]


# ---------------------------------------------------------------------------
# API plumbing + stacked sharding layouts
# ---------------------------------------------------------------------------
def test_execspec_sweep_mode_plumbing():
    from repro.api import ExecSpec, UnlearnSpec
    spec = UnlearnSpec.for_mode("ficabu", sweep_mode="scanned")
    assert spec.exec.sweep_mode == "scanned"
    assert spec.to_config().sweep_mode == "scanned"
    assert UnlearnSpec().to_config().sweep_mode == "layerwise"
    rt = UnlearnSpec.from_json(spec.to_json())
    assert rt == spec and rt.exec.sweep_mode == "scanned"
    with pytest.raises(ValueError, match="sweep_mode"):
        ExecSpec(sweep_mode="fused")
    # the engine-level config validates too — a typo must not silently
    # degrade to the layerwise loop
    with pytest.raises(ValueError, match="sweep_mode"):
        cau.UnlearnConfig(sweep_mode="Scanned")


def test_stacked_param_pspecs():
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as shd

    class FakeMesh:
        shape = {"data": 2, "model": 4}

    stack = {"mixer": {"wq": jnp.zeros((6, 32, 64))},   # [L, in, out]
             "ln": {"scale": jnp.zeros((6, 32))}}
    specs = shd.stacked_param_pspecs(stack, None, mode="tp")
    assert specs["mixer"]["wq"] == P(None, "data", "model")
    assert specs["ln"]["scale"] == P(None, None)
    # divisibility fitting: a mesh axis that does not divide the layer dims
    # degrades to replication, the stack dim stays replicated
    fitted = shd.stacked_param_pspecs(
        {"w": jnp.zeros((6, 31, 64))}, FakeMesh, mode="tp")
    assert fitted["w"] == P(None, None, "model")
    fsdp = shd.stacked_param_pspecs(stack, FakeMesh, mode="fsdp")
    assert fsdp["mixer"]["wq"][0] is None


def test_effective_tau32_matches_host_compare():
    from repro.engine import effective_tau32
    for tau in (0.6, 0.05, -1.0, 1 / 3, 0.5):
        t32 = effective_tau32(tau)
        for a in (np.float32(tau), np.float32(tau) * (1 + 1e-7),
                  np.nextafter(np.float32(tau), np.float32(-np.inf)),
                  np.nextafter(np.float32(tau), np.float32(np.inf))):
            assert (a <= t32) == (float(a) <= tau), (tau, a)
