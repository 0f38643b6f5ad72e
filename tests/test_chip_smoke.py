"""chip_smoke.py stays runnable: its serve phase at the smoke config on the
CPU, the size switch it relies on, and its refusal to run anywhere but a
TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_smoke_on_cpu(chip_smoke):
    res = chip_smoke.serve_phase("--smoke", platform="cpu")
    assert res["publications"] >= 2
    assert res["serve_spec"]["publish_lag"] == 2


class _Built(Exception):
    """Raised by the patched model init: the config is all we look at."""


@pytest.mark.parametrize("switch,want", [
    ("--full", (26, 1152, 262144)),
    ("--smoke", (8, 64, 256)),
])
def test_size_switch_selects_config(chip_smoke, monkeypatch, switch, want):
    from repro.launch import serve
    seen = {}

    def init(key, cfg):
        seen["cfg"] = cfg
        raise _Built

    monkeypatch.setattr(serve.LM, "init_lm", init)
    with pytest.raises(_Built):
        serve.main(chip_smoke.serve_argv(switch))
    cfg = seen["cfg"]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == want


def test_size_switches_exclude_each_other(chip_smoke):
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(chip_smoke.serve_argv("--full") + ["--smoke"])


def test_refuses_to_run_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
