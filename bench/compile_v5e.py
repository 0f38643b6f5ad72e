"""Compile each cell's served programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/compile_v5e.py [cell ...]

For each cell (default: every ``bench/workloads/*.json``) it lowers and
compiles, at the cell's shapes and on one v5e chip of a described ``2x2``
topology: the decode step, one prefill block, the admission scatter, the
drain's sweep program and the global Fisher.  It prints each program's
``compiled.memory_analysis()`` and fails where the TPU compiler refuses a
program.  A rehearsal before a chip run: it says what fits, never how
fast anything runs.  It reaches into the program's internals to name those
programs, which the timed harness never does.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _programs(fam, cfg, cm, one_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import system
    _, _, _, LMConfig = system._program()
    from repro.core import adapters, fisher
    from repro.core.schedule import checkpoint_set
    from repro.engine.sweep import SweepPlan, build_sweep_program
    from repro.launch.serve import StreamEngine
    from repro.models import lm as LM

    lc = fam.lm_config(cfg, LMConfig)
    dt = jnp.dtype(cfg["torch_dtype"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    w = {k: sds(s, dt) for k, s in fam.shapes(cfg).items()}
    params = fam.program_tree(w)
    f32 = jax.tree_util.tree_map(lambda a: sds(a.shape, jnp.float32), params)
    P, G, B = cm["prompt_len"], cm["output_len"], cm["pool_width"]
    eng = StreamEngine(params, lc, gen_len=G, prompt_len=P, max_batch=B)
    A = eng.admit_chunk
    as_sds = lambda t: jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), t)
    cache = as_sds(jax.eval_shape(lambda: LM.init_cache(lc, B, P + G)))
    sub = as_sds(jax.eval_shape(lambda: LM.init_cache(lc, A, P + G)))
    i32 = jnp.int32
    yield "decode_step", eng._step_fn.lower(
        params, cache, sds((B, 1), i32), sds((B,), i32), sds((B,), i32),
        sds((B, G), i32))
    yield "prefill_block", LM._prefill_block_jit.lower(
        params, lc, sds((A, eng.prefill_block), i32), sub, sds((), i32),
        True, True)
    yield "admit", eng._admit_fn.lower(
        cache, sub, sds((B, 1), i32), sds((B,), i32), sds((B,), i32),
        sds((B, G), i32), sds((A,), i32), sds((A, 1), i32))
    S = cm["forget_len"]
    n = cm["forget_set"]
    cs = 4
    if float(cm.get("forget_rate", 0)) > 0:
        ad = adapters.lm_adapter(lc, S)
        x = sds((n, S), i32)
        L = ad.n_layers
        # the plan plan_scanned_sweep derives for a one-kind block stack
        # (it indexes concrete layers, which described devices cannot hold)
        plan = SweepPlan(n_layers=L, kinds=(ad.layer_key(1),),
                         rep_depths=(1,), type_ids=(0,) * (L - 2))
        prog = build_sweep_program(ad, plan, n_sets=1,
                                   cps=tuple(checkpoint_set(L, 2)), limit=L,
                                   chunk_size=cs, use_kernel=False)
        yield "sweep", prog.lower(params, params, f32, (x,), (x,),
                                  sds((L, 2), jnp.float32),
                                  sds((), jnp.float32))
        loss = lambda p, b: LM.lm_loss(p, lc, b[0], b[1], aux_weight=0.0)
        r = int(cm["unlearn"]["retain_sample"])
        yield "global_fisher", fisher._diag_fisher_jit.lower(
            loss, params, (sds((r, S), i32), sds((r, S), i32)), cs)
    del np


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from registry import Registry
    jax.config.update("jax_enable_compilation_cache", False)
    reg = Registry()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cells = argv or reg.cells()
    for name in cells:
        cell = reg.cell(name)
        cm = dict(reg.mix(cell["traffic"]), **cell)
        cfg = reg.config(cell["config"])
        for prog, lowered in _programs(reg.family(cfg), cfg, cm, one_chip):
            m = lowered.compile().memory_analysis()
            print(f"{name} {prog}: arguments {m.argument_size_in_bytes} "
                  f"output {m.output_size_in_bytes} temporaries "
                  f"{m.temp_size_in_bytes} peak {m.peak_memory_in_bytes} "
                  f"generated code {m.generated_code_size_in_bytes} bytes",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
