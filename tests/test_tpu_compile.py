"""Compile every Pallas kernel wrapper at gemma3-1b widths for a described
TPU v5e chip (no chip needed): what the chip's compiler refuses — a slice
not aligned to the tiling, too much fast memory — fails here, and each
program must hold the kernel as a ``tpu_custom_call``."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops

FULL = configs.get("gemma3-1b").full
D, F = FULL.d_model, FULL.d_ff
N = 4 * 23          # one forget chunk of 4 sequences x 23 tokens
F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _cases():
    def fimd(dt):
        return ops.fimd, [((4, D * F), dt)]

    def dampen(dt):
        return (lambda th, i_f, i_g: ops.dampen(th, i_f, i_g, 2.0, 0.5),
                [((D, F), dt), ((D, F), F32), ((D, F), F32)])

    def gemm(dt):
        return ops.gemm_fisher, [((N, D), dt), ((N, F), dt)]

    return {
        "fimd-f32": fimd(F32),
        "fimd-bf16": fimd(BF16),
        "dampen-f32": dampen(F32),
        "dampen-bf16": dampen(BF16),
        "dampen_int8": (
            lambda th, i_f, i_g: ops.dampen_int8(th, i_f, i_g, 2.0, 0.5),
            [((D, F), I8), ((D, F), F32), ((D, F), F32)]),
        "dampen_int8_rowscale": (
            lambda th, i_fq, fs, i_g: ops.dampen_int8_rowscale(
                th, i_fq, fs, i_g, 2.0, 0.5),
            [((D, F), I8), ((D, F), I8), ((D,), F32), ((D, F), F32)]),
        "gemm_fisher-f32": gemm(F32),
        "gemm_fisher-bf16": gemm(BF16),
        "gemm_fisher_int8": (
            ops.gemm_fisher_int8,
            [((N, D), I8), ((N, F), I8), ((D,), F32), ((F,), F32)]),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache,
                                 monkeypatch):
    # the wrappers pick interpret mode from the default backend (the CPU
    # here); steer them to the chip's lowering for this compile
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
