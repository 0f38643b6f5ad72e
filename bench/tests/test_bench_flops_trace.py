"""FLOP and byte counters against hand counts at a smoke size; the trace
reduction on a small recorded TPU trace; unknown chips are errors."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from families import dense as flops  # noqa: E402
import harness  # noqa: E402
import xplane  # noqa: E402
from registry import Registry  # noqa: E402

CFG = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
       "vocab_size": 10}


def test_block_and_head_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x12 each
    assert flops.block_matmul_params(CFG) == 64 + 32 + 32 + 64 + 3 * 96
    assert flops.head_params(CFG) == 80


def test_decode_step_flops_by_hand():
    per_row = 2 * (3 * 480 + 80)
    # attention: 2 heads x 4 dims x (QK + PV) x 2 ops x context
    att = lambda c: 3 * (2 * 4 * 2 * 2 * c)
    assert flops.decode_step_flops(CFG, [5]) == per_row + att(5)
    assert (flops.decode_step_flops(CFG, [5, 9])
            == 2 * per_row + att(5) + att(9))
    assert flops.decode_step_flops(CFG, []) == 0


def test_decode_step_bytes_by_hand():
    weights = (3 * 480 + 80) * 2
    kv = lambda c: 3 * 2 * 4 * c * 2
    assert flops.decode_step_bytes(CFG, [5, 7]) == weights + kv(5) + kv(7)


def test_drain_flops_count_no_checkpoint_forwards():
    n, S = 2, 4
    tokens, pairs = n * S, n * S * (S + 1) // 2
    block_fwd = 2 * tokens * 480 + 2 * 4 * 2 * 2 * pairs
    head_fwd = 2 * tokens * 80
    fwd = 3 * block_fwd + head_fwd
    assert flops.forward_flops(CFG, n, S) == fwd
    full = flops.drain_flops(CFG, n, S, blocks_swept=3)
    assert full == fwd + 2 * (3 * block_fwd + head_fwd)
    # a sweep that halts after the head and one block counts less, and
    # nothing is added for the partial-inference checkpoint forwards
    part = flops.drain_flops(CFG, n, S, blocks_swept=1)
    assert part == fwd + 2 * (block_fwd + head_fwd)
    assert part < full


def test_unknown_device_kind_is_an_error():
    peaks = Registry().peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.peak_for(peaks, "TPU v9 imaginary")


def test_union_and_gaps():
    u = xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)])
    assert u == [(0, 3), (5, 7), (10, 11)]
    assert xplane.total(u) == 6
    assert xplane.gaps(u, 0, 12) == [(3, 5), (7, 10), (11, 12)]
    assert xplane.program_name("jit__step(12)") == "jit__step"


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 3000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 3000000 } }
  lines { id: 3 name: "Steps" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__step(3)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_sweep(1)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "dot.2" } }
  event_metadata { key: 5 value { id: 5 name: "step" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.read" } }
  event_metadata { key: 2 value { id: 2 name: "bench.generator" } }
  event_metadata { key: 3 value { id: 3 name: "other.span" } }
}
"""


def test_reduce_profile_reads_planes_lines_and_spans():
    """The reduction through JAX's own ``ProfileData``, on an ``XSpace``
    laid out as a TPU trace is (times in microseconds from the first
    event): programs
    [0, 3) and [9, 12) of ``jit__step``, [5, 7) of ``jit_sweep``; ops cover
    the same; host spans ``bench.read`` [3, 5), ``bench.generator`` [7,
    8.5); a line the reduction does not read (``Steps``) is ignored."""
    from jax.profiler import ProfileData
    tr = xplane.reduce_profile(ProfileData.from_text_proto(XSPACE))
    assert len(tr.devices) == 1
    progs = tr.program_seconds()
    assert progs["jit__step"][1] == 2
    assert progs["jit__step"][0] == pytest.approx(6e-6)
    assert progs["jit_sweep"] == (pytest.approx(2e-6), 1)
    assert tr.busy_s() == pytest.approx(8e-6)
    assert [s[0] for s in tr.host_spans] == ["bench.read", "bench.generator"]
    gaps = dict(tr.idle_gaps())
    assert gaps["bench.read"] == pytest.approx(2e-6)
    assert gaps["bench.generator"] == pytest.approx(2e-6)
    bd = xplane.breakdown(tr)
    assert bd["device_ops"][0][0] == "jit__step:dot.2"
    assert bd["device_ops"][0][1] == pytest.approx(5e-6)


RECORDED = os.path.join(HERE, "data", "internvl2-1b-lm.chat-forget.txtpb.gz")


def test_recorded_tpu_trace_reduces_to_programs_and_readers():
    """An 81 ms cut of a traced run of ``internvl2-1b-lm.chat-forget`` on a
    TPU v5 lite (``bench/tests/cut_trace.py``): one admission (ten prefill
    blocks and the scatter into the pool) among decode steps.  The device
    plane, its program names and the harness's host spans are found, and
    the readers of the decode and admission layers read from them."""
    import cut_trace
    tr = xplane.reduce_profile(cut_trace.load(RECORDED))
    assert len(tr.devices) == 1
    lo, hi = tr.bounds()
    assert 0 < tr.busy_s() <= hi - lo
    progs = tr.program_seconds()
    assert progs["jit__step"][1] == 27
    assert progs["jit_prefill_block"][1] == 10
    assert progs["jit__admit"][1] == 1
    assert {s[0] for s in tr.host_spans} == {
        "bench.generator", "bench.step_once", "bench.read"}
    bd = xplane.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10
    view = harness.RunView(None, None, None, None, [], 0, tr, None, None,
                           None)
    step_ms = Registry().metric_reader("decode_step_ms")(view)
    assert 2.0 < step_ms < 2.5      # 1.26 GB of weights at 819 GB/s: 1.5
    pre = Registry().metric_reader("prefill_ms")(view)
    assert pre == pytest.approx((progs["jit_prefill_block"][0]
                                 + progs["jit__admit"][0]) * 1e3)
    assert view.program("jit_sweep") is None
