"""repro.api tests: the typed spec taxonomy + the Unlearner facade.

  * UnlearnSpec JSON round-trip; validation raises ValueError (not assert)
    with actionable messages;
  * the legacy kwarg entry points (ficabu.unlearn / unlearn_group /
    _mode_config) emit DeprecationWarning and stay BIT-IDENTICAL to the
    spec path, on both a small LM and the trained ResNet;
  * the facade's Fisher lifecycle: computed once, values refreshable,
    structure-locked (the old unlearn_group clobber bug);
  * facade error paths reject with ValueError;
  * the api-gate script (CI boundary check) passes on the tree.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DampenSpec, ExecSpec, ForgetRequest, HaltSpec,
                       UnlearnSpec, Unlearner)
from repro.core import adapters, cau, ficabu, fisher
from repro.data import synthetic as syn
from repro.models import lm as LM


def _trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture(scope="module")
def lm_setting():
    cfg = LM.LMConfig(name="api-t", n_layers=2, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=64, vocab=64)
    dcfg = syn.LMDataConfig(vocab=64, n_domains=4, seq_len=16,
                            n_per_domain=8, seed=3)
    toks, doms = syn.make_lm_domains(dcfg)
    params = LM.init_lm(jax.random.PRNGKey(0), cfg)
    loss_fn = lambda p, b: LM.lm_loss(p, cfg, b[0], b[1], aux_weight=0.0)
    i_d = fisher.diag_fisher(loss_fn, params, (toks[:, :-1], toks[:, 1:]),
                             chunk_size=4)
    return {"cfg": cfg, "toks": toks, "doms": doms, "params": params,
            "i_d": i_d, "loss_fn": loss_fn,
            "adapter": adapters.lm_adapter(cfg, 16)}


# ---------------------------------------------------------------------------
# spec taxonomy: round trip + validation
# ---------------------------------------------------------------------------
def test_spec_json_round_trip():
    spec = UnlearnSpec.for_mode(
        "ficabu", alpha=3.5, lam=0.7, tau=0.2, checkpoint_every=3, b_r=4.0,
        c_m=2.5, max_layers=7, chunk_size=2, use_kernel=True, donate=True,
        mesh_axes=("data", "model"), sharding="fsdp", cache_dir="/tmp/c")
    again = UnlearnSpec.from_json(spec.to_json())
    assert again == spec
    assert again.exec.mesh_axes == ("data", "model")  # list -> tuple
    assert UnlearnSpec.from_dict(spec.to_dict()) == spec


def test_spec_defaults_round_trip():
    spec = UnlearnSpec()
    assert UnlearnSpec.from_json(spec.to_json()) == spec
    assert spec.mode == "ficabu" and spec.cau_enabled and spec.bd_enabled


def test_spec_accepts_plain_mappings():
    spec = UnlearnSpec(mode="cau", dampen={"alpha": 2.0},
                       halt={"tau": 0.1}, exec={"chunk_size": 2})
    assert isinstance(spec.dampen, DampenSpec)
    assert spec.dampen.alpha == 2.0 and spec.exec.chunk_size == 2


@pytest.mark.parametrize("build", [
    lambda: UnlearnSpec.for_mode("nope"),
    lambda: UnlearnSpec.for_mode("ssd", alpha=0.0),
    lambda: UnlearnSpec.for_mode("ssd", alpha=float("nan")),
    lambda: UnlearnSpec.for_mode("ssd", lam=-1.0),
    lambda: UnlearnSpec.for_mode("ssd", b_r=0.5),
    lambda: UnlearnSpec.for_mode("ssd", checkpoint_every=-1),
    lambda: UnlearnSpec.for_mode("ssd", max_layers=0),
    lambda: UnlearnSpec.for_mode("ssd", chunk_size=0),
    lambda: UnlearnSpec.for_mode("ssd", sharding="zz"),
    lambda: UnlearnSpec.for_mode("ssd", mesh_axes=()),
    lambda: UnlearnSpec.for_mode("ssd", cache_dir=""),
    lambda: UnlearnSpec(mode="ssd", dampen="not-a-spec"),
    lambda: UnlearnSpec.from_dict({"mode": "ssd", "zzz": 1}),
    lambda: UnlearnSpec.from_dict({"dampen": {"alhpa": 1.0}}),
    lambda: UnlearnSpec.from_json("not json"),
    lambda: HaltSpec(checkpoint_every=True),
    lambda: ExecSpec(donate="yes"),
])
def test_spec_validation_rejects(build):
    with pytest.raises(ValueError):
        build()


def test_for_mode_matches_deprecated_mode_config():
    kw = dict(alpha=5.0, lam=0.5, tau=0.3, checkpoint_every=3, b_r=6.0,
              c_m=None, chunk_size=4, use_kernel=False)
    for mode in ("ssd", "cau", "bd", "ficabu"):
        with pytest.warns(DeprecationWarning):
            legacy = ficabu._mode_config(mode, **kw)
        assert UnlearnSpec.for_mode(mode, **kw).to_config() == legacy


def test_mode_semantics_in_to_config():
    cfg = UnlearnSpec.for_mode("bd", tau=0.4, checkpoint_every=2).to_config()
    assert cfg.tau == -1.0 and cfg.checkpoint_every == 0 and cfg.balanced
    cfg = UnlearnSpec.for_mode("cau", tau=0.4, checkpoint_every=2).to_config()
    assert cfg.tau == 0.4 and cfg.checkpoint_every == 2 and not cfg.balanced
    # explicit DampenSpec.balanced overrides the mode
    spec = UnlearnSpec(mode="ssd", dampen=DampenSpec(balanced=True))
    assert spec.to_config().balanced


# ---------------------------------------------------------------------------
# legacy shims: DeprecationWarning + bitwise equivalence
# ---------------------------------------------------------------------------
def test_legacy_unlearn_shim_bitwise_lm(lm_setting):
    m = lm_setting
    fb = m["toks"][:8]
    kw = dict(mode="ficabu", alpha=6.0, lam=0.5, tau=0.6,
              checkpoint_every=1, chunk_size=4)
    with pytest.warns(DeprecationWarning, match="Unlearner.forget"):
        p_old, st_old = ficabu.unlearn(
            m["adapter"], m["params"], m["i_d"], fb[:, :-1], fb[:, 1:], **kw)

    unl = Unlearner(m["adapter"], m["i_d"], UnlearnSpec.for_mode(
        "ficabu", alpha=6.0, lam=0.5, tau=0.6, checkpoint_every=1,
        chunk_size=4))
    p_new, st_new = unl.forget(ForgetRequest(fb[:, :-1], fb[:, 1:]),
                               params=m["params"])
    _trees_equal(p_old, p_new)
    for k in ("selected_per_layer", "stopped_at_l", "forget_acc_trace",
              "macs", "macs_vs_ssd_pct", "mode"):
        assert st_old[k] == st_new[k], k


def test_legacy_unlearn_shim_bitwise_resnet(trained_resnet):
    m = trained_resnet
    splits = syn.split_forget_retain(m["x"], m["y"], forget_class=2)
    fx, fy = splits["forget"]
    i_d = fisher.diag_fisher(m["loss_fn"], m["params"],
                             (m["x"][:32], m["y"][:32]), chunk_size=8)
    adapter = adapters.resnet_adapter(m["cfg"])
    kw = dict(mode="ficabu", alpha=10.0, lam=1.0, tau=1 / 6 + 0.03,
              checkpoint_every=2, chunk_size=8)
    with pytest.warns(DeprecationWarning):
        p_old, st_old = ficabu.unlearn(adapter, m["params"], i_d,
                                       fx[:32], fy[:32], **kw)
    unl = Unlearner(adapter, i_d, UnlearnSpec.for_mode(
        "ficabu", alpha=10.0, lam=1.0, tau=1 / 6 + 0.03, checkpoint_every=2,
        chunk_size=8))
    p_new, st_new = unl.forget(ForgetRequest(fx[:32], fy[:32]),
                               params=m["params"])
    _trees_equal(p_old, p_new)
    assert st_old["selected_per_layer"] == st_new["selected_per_layer"]
    assert st_old["stopped_at_l"] == st_new["stopped_at_l"]
    assert st_old["macs"] == st_new["macs"]


def test_legacy_group_shim_bitwise(lm_setting):
    m = lm_setting
    sets = []
    for d in (1, 2):
        fb = m["toks"][m["doms"] == d][:8]
        sets.append((fb[:, :-1], fb[:, 1:]))
    kw = dict(mode="ficabu", alpha=6.0, lam=0.5, tau=-1.0,
              checkpoint_every=2, chunk_size=4)
    with pytest.warns(DeprecationWarning, match="forget_group"):
        p_old, st_old, g_old = ficabu.unlearn_group(
            m["adapter"], m["params"], m["i_d"], sets, **kw)
    unl = Unlearner(m["adapter"], m["i_d"], UnlearnSpec.for_mode(
        "ficabu", alpha=6.0, lam=0.5, tau=-1.0, checkpoint_every=2,
        chunk_size=4))
    p_new, st_new, g_new = unl.forget_group(sets, params=m["params"])
    _trees_equal(p_old, p_new)
    assert [s["selected_per_layer"] for s in st_old] == \
        [s["selected_per_layer"] for s in st_new]
    assert g_old["stopped_at_l"] == g_new["stopped_at_l"]
    assert g_old["mode"] == g_new["mode"] == "ficabu"


# ---------------------------------------------------------------------------
# Fisher lifecycle: once, refreshable, structure-locked
# ---------------------------------------------------------------------------
def test_fisher_structure_clobber_rejected(lm_setting):
    m = lm_setting
    unl = Unlearner(m["adapter"], m["i_d"])
    # value refresh with the same structure is allowed (streamed refresh)
    refreshed = jax.tree_util.tree_map(lambda x: x * 2.0, m["i_d"])
    unl.set_fisher(refreshed)
    # structurally different tree: rejected, not clobbered
    with pytest.raises(ValueError, match="structurally different"):
        unl.set_fisher({"w": jnp.ones((3,))})
    assert unl.fisher_global is refreshed


def test_group_shim_rejects_structural_fisher_swap(lm_setting):
    """The old bug: unlearn_group(session=...) silently overwrote
    session.fisher_global. A structurally different tree must now raise."""
    m = lm_setting
    fb = m["toks"][:8]
    unl = Unlearner(m["adapter"], m["i_d"], UnlearnSpec.for_mode(
        "ficabu", tau=-1.0, checkpoint_every=2, chunk_size=4))
    unl.forget_group([(fb[:, :-1], fb[:, 1:])], params=m["params"])
    sess = unl.session
    with pytest.raises(ValueError, match="structurally different"):
        with pytest.warns(DeprecationWarning):
            ficabu.unlearn_group(
                m["adapter"], m["params"], {"w": jnp.ones((4,))},
                [(fb[:, :-1], fb[:, 1:])], session=sess)
    # the warm session's Fisher is untouched
    assert sess.fisher_global is unl.fisher_global


def test_ensure_fisher_computes_once(lm_setting):
    m = lm_setting
    unl = Unlearner(m["adapter"])
    t = m["toks"]
    i1 = unl.ensure_fisher(m["loss_fn"], m["params"], (t[:8, :-1], t[:8, 1:]),
                           chunk_size=4)
    i2 = unl.ensure_fisher(m["loss_fn"], m["params"],
                           (t[8:16, :-1], t[8:16, 1:]), chunk_size=4)
    assert i1 is i2  # second call is a no-op: once per served model


# ---------------------------------------------------------------------------
# facade error paths: ValueError with actionable messages
# ---------------------------------------------------------------------------
def test_facade_error_paths(lm_setting):
    m = lm_setting
    other = adapters.lm_adapter(m["cfg"], 16)
    unl = Unlearner(m["adapter"], m["i_d"])
    unl._ensure_session()
    with pytest.raises(ValueError, match="bound to adapter"):
        Unlearner(other, m["i_d"], session=unl.session)
    with pytest.raises(ValueError, match="at least one"):
        unl.forget_group([], params=m["params"])
    with pytest.raises(ValueError, match="ForgetRequest"):
        unl.forget("not-a-request", params=m["params"])
    with pytest.raises(ValueError, match="no global Fisher"):
        Unlearner(m["adapter"]).forget(
            ForgetRequest(m["toks"][:8, :-1], m["toks"][:8, 1:]),
            params=m["params"])
    with pytest.raises(ValueError, match="ModelAdapter"):
        Unlearner("not-an-adapter")
    with pytest.raises(ValueError, match="UnlearnSpec"):
        Unlearner(m["adapter"], m["i_d"], spec={"mode": "ssd"})


def test_enable_compilation_cache_conflicting_dir_rejected(tmp_path):
    """The persistent cache is process-global: repointing it at a second
    dir must raise, not silently intermix two facades' entries."""
    import jax as _jax
    from repro.api import enable_compilation_cache
    current = _jax.config.jax_compilation_cache_dir
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the environment places the cache: no argument repoints it
        enable_compilation_cache(str(tmp_path / "other-cache"))
        assert _jax.config.jax_compilation_cache_dir == current
    elif current:
        other = str(tmp_path / "other-cache")
        with pytest.raises(ValueError, match="process-global"):
            enable_compilation_cache(other)
        # same dir stays idempotent
        enable_compilation_cache(current)
    else:
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        enable_compilation_cache(a)
        try:
            with pytest.raises(ValueError, match="process-global"):
                enable_compilation_cache(b)
            enable_compilation_cache(a)  # idempotent for the same dir
        finally:
            _jax.config.update("jax_compilation_cache_dir", None)


def test_environment_cache_dir_wins(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, the persistent cache lives
    there: neither an argument nor ExecSpec.cache_dir places another."""
    import jax as _jax
    from repro.api import enable_compilation_cache, resolve_cache_dir
    from repro.api.facade import DEFAULT_CACHE_DIR
    before = _jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "env-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        assert resolve_cache_dir(str(tmp_path / "arg")) == env_dir
        assert enable_compilation_cache(str(tmp_path / "arg")) == 0
        assert _jax.config.jax_compilation_cache_dir == env_dir
        assert os.path.isdir(env_dir)
        assert not (tmp_path / "arg").exists()
    finally:
        _jax.config.update("jax_compilation_cache_dir", before)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert resolve_cache_dir("mine") == "mine"
    assert resolve_cache_dir() == DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == str(Path(__file__).resolve().parent.parent
                                    / ".jax_cache")


def test_auto_midpoint_actionable_error():
    with pytest.raises(ValueError, match="selected_per_layer"):
        ficabu.auto_midpoint({"stopped_at_l": 3})
    with pytest.raises(ValueError, match="selected_per_layer"):
        ficabu.auto_midpoint(None)


def test_session_rejects_empty_group(lm_setting):
    m = lm_setting
    unl = Unlearner(m["adapter"], m["i_d"])
    sess = unl._ensure_session()
    with pytest.raises(ValueError, match="at least one"):
        sess.forget_many(m["params"], [], UnlearnSpec().to_config())


# ---------------------------------------------------------------------------
# with_spec: sibling facades share one warm session
# ---------------------------------------------------------------------------
def test_with_spec_shares_warm_session(lm_setting):
    m = lm_setting
    fb = m["toks"][:8]
    unl_ssd = Unlearner(m["adapter"], m["i_d"],
                        UnlearnSpec.for_mode("ssd", chunk_size=4))
    unl_fic = unl_ssd.with_spec(UnlearnSpec.for_mode(
        "ficabu", tau=-1.0, checkpoint_every=2, chunk_size=4))
    assert unl_fic.session is unl_ssd.session
    _, st1 = unl_ssd.forget((fb[:, :-1], fb[:, 1:]), params=m["params"])
    fused_compiles = unl_ssd.stats["fused_compiles"]
    _, st2 = unl_fic.forget((fb[:, :-1], fb[:, 1:]), params=m["params"])
    assert st1["mode"] == "ssd" and st2["mode"] == "ficabu"
    # the sibling replays every FUSED program the ssd sweep compiled (the
    # cau mode additionally compiles its checkpoint programs, once)
    assert unl_fic.stats["fused_compiles"] == fused_compiles
    assert st2["engine"]["cache_hits"] > 0


# ---------------------------------------------------------------------------
# CI boundary gate
# ---------------------------------------------------------------------------
def test_api_gate_passes():
    gate = Path(__file__).resolve().parent.parent / "tools" / "api_gate.py"
    res = subprocess.run([sys.executable, str(gate)],
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stdout + res.stderr
