"""A whole run on the CPU at a tiny size (the chip check skipped): sound,
it comes out correct; with the timed path broken underneath, it does not.
And ``run.py`` refuses to measure without a TPU."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from registry import Registry  # noqa: E402


def _run(tmp_path, seed=5, hook=None, control=False):
    import harness
    root = tiny.make_root(str(tmp_path))
    hooks = {"peak": tiny.CPU_PEAK}
    if hook is not None:
        hooks["server"] = hook
    return harness.run("tiny.chat-forget", seed, 3.0, False, root=root,
                       require_tpu=False, hooks=hooks, control=control)


def _state_unchanged(srv):
    srv.svc.run_shadow = lambda payloads, batch: (srv.svc.params, True)


def _publication_lost(srv):
    # the service publishes, but the decode step keeps reading the tree it
    # had: every drain is acknowledged and none reaches the served weights
    eng = srv.engine
    inner = eng._publish_due

    def publish_due(step):
        kept = eng.params
        inner(step)
        eng.params = kept

    eng._publish_due = publish_due


def _half_forget_set(srv):
    srv.svc._rt.max_forget_samples //= 2


def _wrap_step(srv, fn):
    import jax.numpy as jnp
    eng = srv.engine
    inner = eng._step_fn

    def step(params, cache, tok, pos, gidx, outbuf):
        cache, ntok, pos2, gidx2, out = inner(params, cache, tok, pos, gidx,
                                              outbuf)
        bad = fn(jnp, tok, ntok)
        rows = jnp.arange(bad.shape[0])
        out = out.at[rows, gidx].set(bad[:, 0], mode="drop")
        return cache, bad, pos2, gidx2, out

    eng._step_fn = step


def _token_altered(srv):
    _wrap_step(srv, lambda jnp, tok, ntok: (ntok + 1) % 256)


def _half_batch(srv):
    # every other row of the pool (row 0 among them) is left out of the
    # step: it repeats its last token instead of the one the model produces
    _wrap_step(srv, lambda jnp, tok, ntok: jnp.where(
        (jnp.arange(ntok.shape[0]) % 2 == 0)[:, None], tok, ntok))


def test_sound_run_is_correct(tmp_path, restore_jax_cache):
    out = _run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"decode_gap", "edit_mismatch",
                                  "drain_mismatch", "unpublished_drains"}
    assert "setup_s" in out["metrics"] and "tpot_mean_ms" in out["metrics"]
    assert out["checks"]["drain_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", [_state_unchanged, _publication_lost,
                                   _half_forget_set, _token_altered,
                                   _half_batch],
                         ids=["state_unchanged", "publication_lost",
                              "half_forget_set", "token_altered",
                              "half_batch"])
def test_broken_path_is_not_correct(tmp_path, restore_jax_cache, fault):
    out = _run(tmp_path, hook=fault)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct(tmp_path, restore_jax_cache):
    """The lower-precision control (int8 drains, and the fp8 reference's
    first token read as the served one) in the program's place."""
    out = _run(tmp_path, control=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["edit_mismatch"]["value"] > \
        out["checks"]["edit_mismatch"]["limit"]


def test_traced_run_hands_readers_the_window_counters(tmp_path,
                                                     restore_jax_cache):
    """A traced run hands its readers the engine's and the drain worker's
    counters over the window (its steps are the window's steps, its
    publications one per forget request) and the program's own spans."""
    import harness
    root = tiny.make_root(str(tmp_path))
    got = {}
    out = harness.run("tiny.chat-forget", 7, 3.0, True, root=root,
                      require_tpu=False,
                      hooks={"peak": tiny.CPU_PEAK,
                             "view": lambda v: got.update(view=v)})
    assert out["correct"], out["checks"]
    view = got["view"]
    win, eng = view.window, view.counters["engine"]
    assert eng["steps"] == win.last_step - win.first_step > 0
    assert eng["publications"] == len(win.forget_due) > 0
    assert view.counters["drain"]["groups"] == len(win.forget_due)
    names = {s.name for s in view.program_trace.spans}
    assert {"ficabu.engine.step", "ficabu.drain"} <= names
    reg = Registry(root)
    for m in ("step_host_ms", "admit_host_ms", "drain_host_ms"):
        assert reg.metric_reader(m)(view) > 0, m


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "yi-6b-s2.chat-forget", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
