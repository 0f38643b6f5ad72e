#!/usr/bin/env python
"""One-chip smoke run of the served path at gemma3-1b's published widths.

    python chip_smoke.py

Runs on a TPU only: with no TPU it exits 1 before doing anything else.
Two phases, in this one process:

  * serve — ``repro.launch.serve.main`` in ``--serve-mode stream`` on the
    full gemma3-1b config (26 layers, d_model 1152, vocab 262144, bf16,
    random weights from seed 0): continuous-batching decode with two forget
    bursts drained on a shadow tree through ``Fleet``/``DrainScheduler``
    and the scanned sweep, each published between decode steps;
  * kernels — each ``repro.kernels.ops`` wrapper once at gemma3-1b widths,
    compiled for the chip (not interpreted), against ``repro.kernels.ref``.

Earlier lines report widths, parameter bytes, peak device memory, compile
counts and seconds, and each phase's wall time (every phase ends in a host
read of its results).  The last line is one JSON object naming the device.
The persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` when
set, else the checkout's ``.jax_cache``.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "gemma3-1b"
PROMPT_LEN = 16
GEN_LEN = 8
REQUESTS = 8          # the stream serves 3x this many sequences
FORGET_BURSTS = "1;2"  # two bursts -> two drains -> two publications
# random weights already meet any positive forget-accuracy target at the
# first checkpoint, so nothing would be edited: sweep every layer instead
TAU = "-1"

_compiles = {"n": 0, "seconds": 0.0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        _compiles["n"] += 1
        _compiles["seconds"] += duration


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


def serve_argv(size: str = "--full") -> list:
    return ["--arch", ARCH, size, "--serve-mode", "stream",
            "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
            "--gen-len", str(GEN_LEN), "--max-batch", "8",
            "--admit-chunk", "4", "--publish-lag", "2",
            "--unlearn-after", "1", "--forget-domains", FORGET_BURSTS,
            "--tau", TAU]


def serve_phase(size: str = "--full", platform: str = "tpu") -> dict:
    """Serve through the stream engine and check what came out; returns the
    serve result.  ``platform`` is where the served weights must live."""
    from repro.launch import serve

    res = serve.main(serve_argv(size))
    n_seq = 3 * REQUESTS
    _check(res["sequences"] == n_seq,
           f"served {res['sequences']} of {n_seq} sequences")
    _check(res["tokens"] == n_seq * GEN_LEN,
           f"{res['tokens']} tokens for {n_seq} x {GEN_LEN}")
    _check(res["drain_aborts"] == 0 and res["dead_letters"] == 0,
           f"{res['drain_aborts']} drain abort(s), {res['dead_letters']} "
           f"dead letter(s): {res['drain_abort_log']}")
    groups = res["group_log"]
    _check(res["publications"] == res["coalesced_groups"] == len(groups)
           >= 2, f"{res['publications']} publication(s) for "
           f"{res['coalesced_groups']} drain group(s); want equal and >= 2")
    for g in groups:
        eng = g["engine"]
        _check(eng["sweep_mode"] == "scanned" and eng["sweep_launches"] == 1,
               f"drain {g['group']} ran {eng['sweep_launches']} launch(es) "
               f"in {eng['sweep_mode']!r} mode; want one scanned launch")
    _check(all(g["engine"]["compiles"] == 0 for g in groups[1:]),
           f"drains after the first compiled: "
           f"{[g['engine']['compiles'] for g in groups]}")
    _check(res["decode_compile_signatures"] == 1,
           f"decode step compiled {res['decode_compile_signatures']} "
           "signatures across publications")
    w = res["weights"]
    _check(w["changed"], "the published weights equal the initial ones")
    _check(w["finite"], "the published weights hold non-finite values")
    _check(w["platforms"] == [platform],
           f"the served weights live on {w['platforms']}, not {platform}")
    return res


def kernel_phase() -> list:
    """Each ops wrapper once at gemma3-1b widths against its reference,
    under the tolerances tests/test_kernels.py uses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.kernels import ops, ref

    _check(not ops._interpret(), "Pallas kernels would run interpreted")
    cfg = configs.get(ARCH).full
    d, f = cfg.d_model, cfg.d_ff
    n = 4 * (PROMPT_LEN + GEN_LEN - 1)   # one forget chunk's tokens
    k = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8

    def normal(shape, dtype):
        return jax.random.normal(next(k), shape, f32).astype(dtype)

    def fisher(shape):
        return jnp.abs(normal(shape, f32)) + 1e-6

    def codes(shape):
        return jax.random.randint(next(k), shape, -127, 128).astype(i8)

    def close(got, want, rtol, atol):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=rtol, atol=atol)

    def exact(got, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    done = []
    hi = jax.default_matmul_precision("highest")

    g = normal((4, d * f), bf16)
    close(ops.fimd(g), ref.fimd_ref(g), 2e-2, 1e-3)
    done.append(("fimd", (4, d * f)))

    th, i_f, i_g = normal((d, f), bf16), fisher((d, f)), fisher((d, f))
    new, mask = ops.dampen(th, i_f, i_g, 2.0, 0.5)
    close(new, ref.dampen_ref(th, i_f, i_g, 2.0, 0.5), 2e-2, 1e-4)
    exact(mask, np.asarray(i_f) > 2.0 * np.asarray(i_g))
    done.append(("dampen", (d, f)))

    thq = codes((d, f))
    exact(ops.dampen_int8(thq, i_f, i_g, 2.0, 0.5),
          ref.dampen_int8_ref(thq, i_f, i_g, 2.0, 0.5))
    done.append(("dampen_int8", (d, f)))

    a, gr = normal((n, d), bf16), normal((n, f), bf16)
    dw, fish = ops.gemm_fisher(a, gr)
    with hi:
        dwr, fishr = ref.gemm_fisher_ref(a, gr)
    close(dw, dwr, 2e-2, 2e-1)
    close(fish, fishr, 4e-2, 2e-1)
    done.append(("gemm_fisher", (n, d, f)))

    aq, gq = codes((n, d)), codes((n, f))
    sa = jnp.abs(normal((d,), f32)) + 1e-3
    sg = jnp.abs(normal((f,), f32)) + 1e-3
    dw, fish = ops.gemm_fisher_int8(aq, gq, sa, sg)
    with hi:
        dwr, fishr = ref.gemm_fisher_int8_ref(aq, gq, sa, sg)
    exact(dw, dwr)
    exact(fish, fishr)
    done.append(("gemm_fisher_int8", (n, d, f)))
    return done


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package under {SRC}: run chip_smoke.py from a "
              "checkout of this repository")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke run never falls back to it")
    sys.path.insert(0, SRC)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    from repro import configs
    from repro.models import lm as LM

    cfg = configs.get(ARCH).full
    shapes = jax.eval_shape(lambda: LM.init_lm(jax.random.PRNGKey(0), cfg))
    n_param = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(shapes))
    print(f"chip_smoke: {ARCH} full: layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, vocab {cfg.vocab}, dtype {cfg.param_dtype}; "
          f"{n_param} parameters, {n_bytes} parameter bytes", flush=True)

    t0 = time.perf_counter()
    res = serve_phase()
    serve_s = time.perf_counter() - t0
    stats = dev.memory_stats() or {}
    eng = res["engine_stats"]
    print(f"chip_smoke: serve phase ok in {serve_s:.3f} s wall: "
          f"{res['sequences']} sequences, {res['tokens']} tokens, "
          f"{res['steps']} engine steps, {res['publications']} "
          f"publications, stop layers "
          f"{[r.get('stopped_at_l') for r in res['unlearn_requests']]}",
          flush=True)
    print(f"chip_smoke: compiles: sweep {eng.get('sweep_compiles')}, "
          f"per drain {[g['engine']['compiles'] for g in res['group_log']]}"
          f", decode signatures {res['decode_compile_signatures']}; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)

    t0 = time.perf_counter()
    done = kernel_phase()
    kernel_s = time.perf_counter() - t0
    print(f"chip_smoke: kernel phase ok in {kernel_s:.3f} s wall: "
          + ", ".join(f"{name} {shape}" for name, shape in done), flush=True)
    stats = dev.memory_stats() or {}
    print(f"chip_smoke: backend compiles {_compiles['n']} taking "
          f"{_compiles['seconds']:.3f} s; compilation cache "
          f"{res['compilation_cache']['dir']} (+"
          f"{res['compilation_cache']['entries_new']} entries); "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
