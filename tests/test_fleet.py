"""Multi-tenant fleet (repro.fleet) tests:

  * TenantSpec/FleetSpec/ServeSpec: JSON round trip + ValueError validation
    (unique tenant names, known archs, scheduling policy, the process-global
    compilation-cache-dir conflict);
  * cross-tenant compiled-program sharing: a same-family tenant's FIRST
    drain replays the sibling's programs with ZERO compiles, and the shared
    cache's compile count for N same-family tenants equals the N=1 run;
  * distinct families never collide in the shared cache (namespaced keys);
  * tenant isolation: after interleaved drains, a tenant's params and
    Fisher are bit-identical to a solo replay;
  * per-tenant precision mix: an int8 tenant compiles its own program
    family even when an fp32 same-arch sibling is already warm;
  * the DrainScheduler: fair-share vs deadline ordering under bursty load
    with a per-drain group budget;
  * the ForgetService deprecation shim and the tenant-named set_fisher
    structure-lock error.
"""
import jax
import numpy as np
import pytest

from repro.api import ServeSpec, Unlearner, UnlearnSpec
from repro.core import adapters
from repro.data import synthetic as syn
from repro.fleet import (DrainScheduler, Fleet, FleetSpec, TenantSpec)
from repro.models import lm as LM

SEQ = 16


def _spec(**kw):
    base = dict(alpha=8.0, lam=1.0, tau=0.6, checkpoint_every=2,
                chunk_size=4, sweep_mode="scanned")
    base.update(kw)
    return UnlearnSpec.for_mode("ficabu", **base)


def _mk_tenant_data(cfg, seed: int):
    dcfg = syn.LMDataConfig(vocab=cfg.vocab, n_domains=4, seq_len=SEQ,
                            n_per_domain=8, seed=seed)
    toks, doms = syn.make_lm_domains(dcfg)
    params = LM.init_lm(jax.random.PRNGKey(seed), cfg)
    return toks, doms, params


def _add(fleet, name, cfg, seed, **kw):
    toks, doms, params = _mk_tenant_data(cfg, seed)
    return fleet.add_tenant(name, cfg, toks, doms, SEQ, params=params, **kw)


@pytest.fixture(scope="module")
def tiny_cfg():
    return LM.LMConfig(name="fleet-t", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, d_ff=64, vocab=64)


@pytest.fixture(scope="module")
def other_cfg():
    # a DIFFERENT family: more layers, wider — distinct namespace + shapes
    return LM.LMConfig(name="fleet-o", n_layers=3, d_model=48, n_heads=4,
                       n_kv_heads=2, d_ff=96, vocab=64)


def _trees_bit_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# specs: round trip + validation
# ---------------------------------------------------------------------------
def test_tenant_spec_round_trip():
    t = TenantSpec("acme", arch="gemma3-1b", seed=3, weight=2.0,
                   spec=_spec())
    again = TenantSpec.from_dict(t.to_dict())
    assert again == t
    assert TenantSpec.from_dict({"name": "x"}).arch == "gemma3-1b"


def test_tenant_spec_validation():
    with pytest.raises(ValueError, match="name"):
        TenantSpec("")
    with pytest.raises(ValueError, match="not a known architecture"):
        TenantSpec("a", arch="no-such-arch")
    with pytest.raises(ValueError, match="seed"):
        TenantSpec("a", seed=-1)
    with pytest.raises(ValueError, match="weight"):
        TenantSpec("a", weight=0.0)
    with pytest.raises(ValueError, match="unknown TenantSpec field"):
        TenantSpec.from_dict({"name": "a", "bogus": 1})


def test_fleet_spec_round_trip():
    f = FleetSpec(tenants=(TenantSpec("a"), TenantSpec("b", seed=1)),
                  serve=ServeSpec(chunk_size=2, refresh_every=1),
                  scheduling="deadline", max_groups_per_drain=1)
    again = FleetSpec.from_json(f.to_json())
    assert again == f
    assert again.serve.chunk_size == 2
    assert again.tenant("b").seed == 1
    with pytest.raises(ValueError, match="no tenant"):
        again.tenant("zzz")


def test_fleet_spec_validation():
    with pytest.raises(ValueError, match="non-empty"):
        FleetSpec(tenants=())
    with pytest.raises(ValueError, match="unique"):
        FleetSpec(tenants=(TenantSpec("a"), TenantSpec("a", seed=1)))
    with pytest.raises(ValueError, match="scheduling"):
        FleetSpec(tenants=(TenantSpec("a"),), scheduling="lifo")
    with pytest.raises(ValueError, match="max_groups_per_drain"):
        FleetSpec(tenants=(TenantSpec("a"),), max_groups_per_drain=-1)
    with pytest.raises(ValueError, match="not valid JSON"):
        FleetSpec.from_json("{nope")


def test_fleet_spec_cache_dir_conflict():
    # the XLA compilation cache is process-global: a tenant pinning its own
    # dir against the fleet's is a config contradiction, caught up front
    t = TenantSpec("a", spec=_spec(cache_dir="/tmp/mine"))
    with pytest.raises(ValueError, match="process-global"):
        FleetSpec(tenants=(t,), serve=ServeSpec(cache_dir="/tmp/fleet"))
    # matching dirs are fine
    FleetSpec(tenants=(TenantSpec("b", spec=_spec(cache_dir="/tmp/same")),),
              serve=ServeSpec(cache_dir="/tmp/same"))


def test_serve_spec_round_trip_and_validation():
    s = ServeSpec(chunk_size=2, coalesce=False, refresh_every=3,
                  sweep_mode="layerwise", precision="int8",
                  cache_dir="/tmp/c", max_forget_samples=4, tau=-1.0)
    assert ServeSpec.from_json(s.to_json()) == s
    low = s.to_unlearn_spec()
    assert low.exec.chunk_size == 2 and low.exec.precision == "int8"
    assert low.halt.tau == -1.0
    assert ServeSpec().to_unlearn_spec().halt.tau == 0.6
    assert low.refresh is not None and low.refresh.every_drains == 3
    assert ServeSpec().to_unlearn_spec().refresh is None
    with pytest.raises(ValueError, match="chunk_size"):
        ServeSpec(chunk_size=0)
    with pytest.raises(ValueError, match="sweep_mode"):
        ServeSpec(sweep_mode="warp")
    with pytest.raises(ValueError, match="precision"):
        ServeSpec(precision="fp8")
    with pytest.raises(ValueError, match="max_forget_samples"):
        ServeSpec(max_forget_samples=0)
    with pytest.raises(ValueError, match="tau"):
        ServeSpec(tau=float("nan"))


# ---------------------------------------------------------------------------
# the scheduler: fairness vs deadlines under bursty load
# ---------------------------------------------------------------------------
def test_scheduler_validation():
    with pytest.raises(ValueError, match="policy"):
        DrainScheduler("lifo")
    s = DrainScheduler("fair")
    s.register("a")
    with pytest.raises(ValueError, match="already registered"):
        s.register("a")
    with pytest.raises(ValueError, match="unknown tenant"):
        s.submit("ghost", 1, due_batch=1)
    with pytest.raises(ValueError, match="weight"):
        s.register("b", weight=-1.0)


def test_scheduler_coalesces_within_tenant():
    s = DrainScheduler("fair")
    s.register("a")
    s.register("b")
    s.submit("a", "d1", due_batch=1)
    s.submit("a", "d2", due_batch=1)
    s.submit("b", "d3", due_batch=2)
    groups = s.due_groups(1)
    assert len(groups) == 1  # b not due yet
    assert groups[0].tenant == "a" and groups[0].payloads == ("d1", "d2")
    assert s.pending() == 1 and s.next_due() == 2
    assert [g.tenant for g in s.due_groups(2)] == ["b"]
    assert s.pending() == 0 and s.next_due() is None


def test_scheduler_fair_share_vs_deadline_ordering():
    """Two tenants flood one request per batch under a one-group-per-drain
    budget.  FAIR honors weights — the weight-3 tenant drains ~3x as often
    — while DEADLINE ignores them and alternates on deadline age.  Neither
    policy starves the light tenant (its deferred deadlines age and its
    virtual time stays untouched)."""
    def run(policy):
        s = DrainScheduler(policy, max_groups=1)
        s.register("heavy", weight=3.0)
        s.register("light", weight=1.0)
        order = []
        for batch in range(1, 9):
            s.submit("heavy", f"h{batch}", due_batch=batch)
            s.submit("light", f"l{batch}", due_batch=batch)
            for g in s.due_groups(batch):
                order.append(g.tenant)
        return order, s
    fair_order, fair_s = run("fair")
    dl_order, _ = run("deadline")
    assert len(fair_order) == len(dl_order) == 8  # one group per drain
    # deadline: weight-blind — deferred deadlines age, the tenants alternate
    assert dl_order.count("heavy") == dl_order.count("light") == 4
    # fair: the weight-3 tenant is served ~3x as often...
    assert fair_order.count("heavy") >= 5, fair_order
    # ...but the light tenant is NOT starved
    assert fair_order.count("light") >= 2, fair_order
    assert fair_s.deferrals > 0


def test_scheduler_weight_biases_fair_share():
    s = DrainScheduler("fair", max_groups=1)
    s.register("heavy", weight=4.0)
    s.register("light", weight=1.0)
    for k in range(4):
        s.submit("heavy", f"h{k}", due_batch=1)
        s.submit("light", f"l{k}", due_batch=1)
    # both due, equal vtime=0: tie-break is earliest due then admission
    # order, then each drain advances the served tenant by n/weight — the
    # heavy tenant re-wins sooner after serving equal work
    first = s.due_groups(1)[0]
    served_heavy = len(first.payloads) if first.tenant == "heavy" else 0
    snap = s.snapshot()
    assert snap["pending"]["heavy"] + snap["pending"]["light"] == \
        8 - len(first.payloads)
    if served_heavy:
        assert snap["vtime"]["heavy"] == served_heavy / 4.0


# ---------------------------------------------------------------------------
# cross-tenant program sharing + isolation (real engine drains)
# ---------------------------------------------------------------------------
def test_same_family_tenants_share_programs(tiny_cfg):
    fleet = Fleet()
    _add(fleet, "a", tiny_cfg, seed=0, spec=_spec())
    _add(fleet, "b", tiny_cfg, seed=1, spec=_spec())
    fleet.submit("a", 1, due_batch=1)
    fleet.submit("b", 1, due_batch=1)
    entries = fleet.drain(1)
    assert [e["tenant"] for e in entries] == ["a", "b"]
    ga = fleet.tenants["a"].group_log[-1]["engine"]
    gb = fleet.tenants["b"].group_log[-1]["engine"]
    assert ga["compiles"] > 0                     # first of the family pays
    assert gb["compiles"] == 0 and gb["cache_hits"] > 0, gb  # b rides free
    # N=2 same-family tenants compiled exactly the N=1 program set
    solo = Fleet()
    _add(solo, "only", tiny_cfg, seed=1, spec=_spec())
    solo.submit("only", 1, due_batch=1)
    solo.drain(1)
    assert fleet.programs.compiles == solo.programs.compiles
    assert fleet.programs.sessions == 2
    # and the tenants' weights stayed their own (different seeds)
    la = jax.tree_util.tree_leaves(fleet.tenants["a"].params)
    lb = jax.tree_util.tree_leaves(fleet.tenants["b"].params)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def test_distinct_family_tenants_do_not_collide(tiny_cfg, other_cfg):
    fleet = Fleet()
    _add(fleet, "a", tiny_cfg, seed=0, spec=_spec())
    _add(fleet, "o", other_cfg, seed=0, spec=_spec())
    fleet.submit("a", 1, due_batch=1)
    fleet.submit("o", 1, due_batch=1)
    fleet.drain(1)
    go = fleet.tenants["o"].group_log[-1]["engine"]
    assert go["compiles"] > 0, "different family must compile its own"
    fams = fleet.family_program_counts()
    assert len(fams) == 2
    assert {ns[0] for ns in fams} == {"fleet-t", "fleet-o"}


def test_tenant_isolation_bit_exact_after_interleaved_drains(tiny_cfg):
    fleet = Fleet()
    _add(fleet, "a", tiny_cfg, seed=0, spec=_spec())
    _add(fleet, "b", tiny_cfg, seed=1, spec=_spec())
    for due, dom in ((1, 1), (2, 2)):
        fleet.submit("a", dom, due_batch=due)
        fleet.submit("b", dom, due_batch=due)
    fleet.drain(1)
    fleet.drain(2)
    # replay tenant b ALONE on a fresh cache, exactly its drain groups
    solo = Fleet()
    rt = _add(solo, "b", tiny_cfg, seed=1, spec=_spec())
    for e in fleet.drain_log:
        if e["tenant"] == "b":
            rt.params, _ = rt.run_due(rt.params, e["payloads"], e["batch"])
    _trees_bit_equal(fleet.tenants["b"].params, rt.params)
    _trees_bit_equal(fleet.tenants["b"].unlearner.fisher_global,
                     rt.unlearner.fisher_global)


def test_per_tenant_precision_mix(tiny_cfg):
    fleet = Fleet()
    _add(fleet, "fp", tiny_cfg, seed=0, spec=_spec())
    _add(fleet, "q", tiny_cfg, seed=0, spec=_spec(precision="int8"))
    fleet.submit("fp", 1, due_batch=1)
    fleet.submit("q", 1, due_batch=1)
    fleet.drain(1)
    gq = fleet.tenants["q"].group_log[-1]["engine"]
    assert gq["precision"] == "int8"
    # int8 is its OWN program family: the warm fp32 sibling must not be
    # mistaken for it (keys include precision), so the int8 drain compiles
    assert gq["compiles"] > 0, gq
    assert fleet.tenants["fp"].group_log[-1]["engine"]["precision"] == "fp32"


def test_fleet_from_spec_builder_contract(tiny_cfg):
    fspec = FleetSpec(tenants=(TenantSpec("a"),))
    with pytest.raises(ValueError, match="missing"):
        Fleet.from_spec(fspec, lambda t: {"cfg": tiny_cfg})
    with pytest.raises(ValueError, match="FleetSpec"):
        Fleet.from_spec({"tenants": []}, lambda t: {})


def test_fleet_rejects_duplicates_and_unknowns(tiny_cfg):
    fleet = Fleet()
    _add(fleet, "a", tiny_cfg, seed=0, spec=_spec())
    with pytest.raises(ValueError, match="already in this fleet"):
        _add(fleet, "a", tiny_cfg, seed=1, spec=_spec())
    with pytest.raises(ValueError, match="no tenant"):
        fleet.submit("ghost", 1, due_batch=1)
    with pytest.raises(ValueError, match="needs an UnlearnSpec"):
        _add(fleet, "nospec", tiny_cfg, seed=0)


# ---------------------------------------------------------------------------
# facade plumbing: tenant-named errors + the ForgetService shim
# ---------------------------------------------------------------------------
def test_set_fisher_error_names_tenant(tiny_cfg):
    toks, _, params = _mk_tenant_data(tiny_cfg, seed=0)
    adapter = adapters.lm_adapter(tiny_cfg, SEQ - 1)
    unl = Unlearner(adapter, spec=_spec(), name="acme")
    unl.ensure_fisher(
        lambda p, b: LM.lm_loss(p, tiny_cfg, b[0], b[1], aux_weight=0.0),
        params, (toks[:, :-1], toks[:, 1:]))
    bad = {"not": np.zeros((2, 2), np.float32)}
    with pytest.raises(ValueError, match="tenant 'acme'"):
        unl.set_fisher(bad)
    # unlabelled facades keep the model-only wording
    unl2 = Unlearner(adapter, spec=_spec())
    unl2.set_fisher(unl.fisher_global)
    with pytest.raises(ValueError, match="model 'fleet-t'"):
        unl2.set_fisher(bad)


def test_forget_service_deprecation_shim(tiny_cfg):
    from repro.launch.serve import ForgetService
    toks, doms, _ = _mk_tenant_data(tiny_cfg, seed=0)
    legacy_spec = _spec()
    with pytest.warns(DeprecationWarning, match="ServeSpec"):
        svc = ForgetService(tiny_cfg, toks, doms, SEQ, legacy_spec)
    assert svc.spec == legacy_spec            # UnlearnSpec honored verbatim
    assert svc.serve_spec.chunk_size == legacy_spec.exec.chunk_size
    with pytest.warns(DeprecationWarning, match="ServeSpec"):
        ForgetService(tiny_cfg, toks, doms, SEQ, spec=legacy_spec)
    # the new surface: frozen ServeSpec, no warning, queue view intact
    svc2 = ForgetService(tiny_cfg, toks, doms, SEQ,
                         serve=ServeSpec(chunk_size=4))
    svc2.submit(1, due_batch=1)
    assert list(svc2.queue) == [{"domain": 1, "due_batch": 1}]
    assert svc2.groups == 0 and svc2.sweeps == 0
    with pytest.raises(ValueError, match="ServeSpec"):
        ForgetService(tiny_cfg, toks, doms, SEQ, serve="fast-please")
