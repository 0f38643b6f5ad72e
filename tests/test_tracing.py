"""The program's spans and counters (``repro.obs.telemetry.span``,
``StreamEngine.stats``, ``TenantRuntime.stats``) and the sweep's
``checkpoint`` scope:

  * a span emits no telemetry event, and a seeded stream run's
    ``engine_fingerprint`` is the same with a profiler trace active;
  * the engine's admission counters agree with its ``batch.admit`` events;
  * ``publish_waits`` counts the publication deadlines that found the
    drain unfinished: some with a slow shadow runner, none with a fast one;
  * the drain worker's ``drain_s`` holds its ``sweep_wait_s``;
  * the lowered sweep carries ``checkpoint`` in the ``op_name`` of the
    checkpoint evaluations, and not in that of the sweep's other work.
"""
import concurrent.futures
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ServeSpec
from repro.core import adapters
from repro.data import synthetic as syn
from repro.engine.sweep import (CHECKPOINT_SCOPE, build_sweep_program,
                                effective_tau32, plan_scanned_sweep)
from repro.launch.serve import (ForgetService, StreamEngine,
                                engine_fingerprint)
from repro.models import lm as LM
from repro.obs import telemetry as _t

P, G = 8, 6
SEQ = P + G


@pytest.fixture(scope="module")
def cfg():
    return LM.LMConfig(name="trace-t", n_layers=2, d_model=32, n_heads=4,
                       n_kv_heads=2, d_ff=64, vocab=64)


@pytest.fixture(scope="module")
def data(cfg):
    dcfg = syn.LMDataConfig(vocab=cfg.vocab, n_domains=4, seq_len=SEQ,
                            n_per_domain=8, seed=0)
    toks, doms = syn.make_lm_domains(dcfg)
    return toks, doms, LM.init_lm(jax.random.PRNGKey(0), cfg)


def _svc(cfg, data):
    toks, doms, _ = data
    return ForgetService(cfg, toks, doms, SEQ, serve=ServeSpec(chunk_size=4))


def _engine(cfg, data, svc=None, n_seq=5, publish_lag=3):
    toks, _, params = data
    eng = StreamEngine(params, cfg, gen_len=G, prompt_len=P, max_batch=4,
                       admit_chunk=2, publish_lag=publish_lag, service=svc)
    for i in range(n_seq):
        eng.enqueue(i, np.asarray(toks[i % len(toks), :P]))
    return eng


def test_span_emits_nothing_and_fingerprint_unchanged(cfg, data, tmp_path):
    with _t.capture() as cap:
        with _t.span("engine.step", step_num=3):
            with _t.span("engine.admit", seqs=[1, 2], width=4, padded=2):
                pass
    assert cap.events == []

    def run(trace_dir=None):
        svc = _svc(cfg, data)
        svc.submit(1, due_batch=1)
        eng = _engine(cfg, data, svc)
        with _t.capture() as cap:
            if trace_dir is None:
                eng.run()
            else:
                with jax.profiler.trace(str(trace_dir)):
                    eng.run()
        return cap.events

    plain, traced = run(), run(tmp_path)
    assert engine_fingerprint(plain) == engine_fingerprint(traced)
    assert [e["kind"] for e in plain] == [e["kind"] for e in traced]
    assert list(tmp_path.rglob("*.xplane.pb"))   # the profiler did write


def test_admission_counters_match_events(cfg, data):
    eng = _engine(cfg, data, n_seq=5)
    with _t.capture() as cap:
        eng.run()
    admits = [e for e in cap.events if e["kind"] == "batch.admit"]
    st = eng.stats()
    assert st["steps"] == eng.step > 0
    assert st["admissions"] == len(admits)
    assert st["admitted_rows"] == sum(len(e["seqs"]) for e in admits) == 5
    assert st["padded_rows"] == sum(e["padded"] for e in admits) > 0
    assert st["publish_waits"] == 0 and st["publish_wait_s"] == 0.0


def _stub_drains(svc, delay_s):
    def run_shadow(payloads, step):
        time.sleep(delay_s)
        return svc.params, True
    svc.run_shadow = run_shadow


def test_publish_waits_count_unfinished_drains(cfg, data):
    # slow: the deadline, one step after the fire, finds the sweep running
    svc = _svc(cfg, data)
    _stub_drains(svc, 0.3)
    for k in range(2):
        svc.submit(1, due_batch=k)
    eng = _engine(cfg, data, svc, publish_lag=1)
    eng.run()
    st = eng.stats()
    assert st["publications"] == 2
    assert st["publish_waits"] >= 1
    assert st["publish_wait_s"] > 0.1 * st["publish_waits"]

    # fast: every drain is joined before its deadline comes
    svc = _svc(cfg, data)
    _stub_drains(svc, 0.0)
    svc.submit(1, due_batch=1)
    eng = _engine(cfg, data, svc, publish_lag=2)
    for _ in range(8):
        eng.step_once()
        concurrent.futures.wait([p[1] for p in eng._pending_pubs],
                                timeout=10)
    assert eng.stats()["publications"] == 1
    assert eng.stats()["publish_waits"] == 0
    assert eng.stats()["publish_wait_s"] == 0.0


def test_drain_counters(cfg, data):
    _, _, params = data
    svc = _svc(cfg, data)
    svc.install_params(params)
    st0 = svc.stats()
    assert st0["drain_s"] == 0.0 and st0["sweep_wait_s"] == 0.0
    _, ran = svc.run_shadow([1], 0)
    assert ran
    st = svc.stats()
    assert st["groups"] == 1 and st["sweeps"] == 1
    assert 0.0 < st["sweep_wait_s"] < st["drain_s"]
    assert st["sweep_wait_s"] == st["engine"]["sweep_wait_s"]
    assert svc._fleet.stats()["tenants"]["default"] == st


def test_sweep_checkpoint_scope_in_hlo(cfg, data):
    toks, _, params = data
    adapter = adapters.lm_adapter(cfg, SEQ - 1)
    x, y = jnp.asarray(toks[:4, :-1]), jnp.asarray(toks[:4, 1:])
    plan = plan_scanned_sweep(adapter, params, x)
    L = adapter.n_layers
    fisher = jax.tree_util.tree_map(jnp.ones_like, params)
    scal = np.ones((L, 2), np.float32)

    prog = build_sweep_program(adapter, plan, n_sets=1,
                               cps=tuple(range(1, L + 1)), limit=L,
                               chunk_size=4, use_kernel=False)
    text = prog.lower(params, params, fisher, (x,), (y,), scal,
                      effective_tau32(-1.0)).as_text(debug_info=True)
    every = set(re.findall(r'loc\("([^"]+)"', text))
    scope = re.compile(r"(^|/)" + CHECKPOINT_SCOPE + r"(/|$)")
    scoped = {n for n in every if scope.search(n)}
    # the head and full-tree checkpoints at the top level, the suffix walk
    # inside the scan's conditional
    assert any(n.startswith("jit(sweep)/" + CHECKPOINT_SCOPE)
               for n in scoped)
    assert any("cond/" in n for n in scoped)
    # the vjp, Fisher and dampening work stays outside the scope
    assert any("transpose" in n or "mul" in n for n in every - scoped)
