"""The program-span reduction (``bench/spans.py``): spans with their threads
and arguments, leaf-op scopes, the readings and the idle gaps named down to
the engine phase, on a synthetic ``XSpace``, on a CPU profiler trace of the
engine, and on recorded TPU traces; the counter readings on counter deltas.
The readings of ``xplane.py`` on the older recording, made before the
program had spans, are pinned too."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import cut_spans  # noqa: E402
import harness  # noqa: E402
import spans  # noqa: E402
import xplane  # noqa: E402
from registry import Registry  # noqa: E402

US = 1_000_000          # picoseconds in a microsecond


def _events(*evs):
    """``(metadata id, start us, duration us[, stats text])`` -> events."""
    out = []
    for mid, a, d, *st in evs:
        out.append(f"events {{ metadata_id: {mid} offset_ps: {int(a * US)} "
                   f"duration_ps: {int(d * US)} {' '.join(st)} }}")
    return "\n    ".join(out)


def _meta(mid, name, stats=""):
    return (f"event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {json.dumps(name)} {stats} }} }}")


def _stat_meta(mid, name):
    return (f"stat_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {json.dumps(name)} }} }}")


def _str(mid, v):
    return f"stats {{ metadata_id: {mid} str_value: {json.dumps(v)} }}"


REF = "jit(sweep)/while/body/cond/branch_1_fun/checkpoint/dot_general:"

# Device (times in us from 1 us): jit__step [0, 2), jit_sweep [6, 16); in
# the sweep a while [6, 16) holds fusion.3 [6, 9) (no scope) and a cond
# [9, 14) holding fusion.5 [9, 12) (scope by a referenced stat on its
# metadata) and fusion.6 [12, 14) (by its event's own stat); fusion.7
# [14, 16) is scoped at the top level, as a TPU trace writes ``tf_op``.
# Host, engine thread: step 0 [0, 7) with decode [0, 0.5) and publish
# [1, 6.8) holding a publish_wait [1.5, 6.5); bench.read [7, 7.5); step 1
# [16, 20) with an admission [16.5, 19.5).  Worker thread: a drain [5, 17)
# with prepare [5, 6), sweep [6, 16.5) holding wait [7, 16.5), finish
# [16.5, 17).
XSPACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    {_events((11, 0, 2), (12, 6, 10))} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {_events((21, 0, 2), (22, 6, 10),
             (23, 6, 3, "stats { metadata_id: 9 int64_value: 5 }"),
             (24, 9, 5), (25, 9, 3),
             (26, 12, 2, _str(1, REF.replace("dot_general:", "add:"))),
             (27, 14, 2))} }}
  {_meta(11, "jit__step(3)")}
  {_meta(12, "jit_sweep(1)")}
  {_meta(21, "fusion.1", _str(1, "jit(_step)/jit(main)/dot_general"))}
  {_meta(22, "while.2", _str(1, "jit(sweep)/while"))}
  {_meta(23, "fusion.3", _str(1, "jit(sweep)/while/body/transpose"))}
  {_meta(24, "cond.4", _str(1, "jit(sweep)/while/body/cond"))}
  {_meta(25, "fusion.5", "stats { metadata_id: 1 ref_value: 8 }")}
  {_meta(26, "fusion.6")}
  {_meta(27, "fusion.7", _str(1, "jit(sweep)/checkpoint/reduce_sum:"))}
  {_stat_meta(1, "tf_op")}
  {_stat_meta(8, REF)}
  {_stat_meta(9, "queue_id")}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "python3" timestamp_ns: 1000
    {_events((31, 0, 7),
             (41, 0, 7, "stats { metadata_id: 3 int64_value: 0 }"),
             (42, 0, 0.5), (43, 1, 5.8),
             (44, 1.5, 5, "stats { metadata_id: 5 int64_value: 0 }",
              "stats { metadata_id: 6 int64_value: 0 }"),
             (32, 7, 0.5),
             (31, 16, 4),
             (41, 16, 4, "stats { metadata_id: 3 int64_value: 1 }"),
             (45, 16.5, 3, _str(4, "[3, 4]")))} }}
  lines {{ id: 8 name: "python3" timestamp_ns: 1000
    {_events((51, 5, 12, "stats { metadata_id: 5 int64_value: 0 }",
              "stats { metadata_id: 6 int64_value: 0 }", _str(7, "[1]")),
             (52, 5, 1), (53, 6, 10.5), (54, 7, 9.5), (55, 16.5, 0.5))} }}
  {_meta(31, "bench.step_once")}
  {_meta(32, "bench.read")}
  {_meta(41, "ficabu.engine.step")}
  {_meta(42, "ficabu.engine.decode")}
  {_meta(43, "ficabu.engine.publish")}
  {_meta(44, "ficabu.engine.publish_wait")}
  {_meta(45, "ficabu.engine.admit")}
  {_meta(51, "ficabu.drain")}
  {_meta(52, "ficabu.drain.prepare")}
  {_meta(53, "ficabu.drain.sweep")}
  {_meta(54, "ficabu.drain.wait")}
  {_meta(55, "ficabu.drain.finish")}
  {_stat_meta(3, "step_num")}
  {_stat_meta(4, "seqs")}
  {_stat_meta(5, "group")}
  {_stat_meta(6, "fire_step")}
  {_stat_meta(7, "payloads")}
}}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return (spans.reduce_xspace(spans.parse_text(XSPACE)),
            xplane.reduce_profile(ProfileData.from_text_proto(XSPACE)))


def test_spans_threads_and_arguments(synthetic):
    pt, _ = synthetic
    by = {}
    for s in pt.spans:
        by.setdefault(s.name, []).append(s)
    engine, worker = ("/host:CPU", 0), ("/host:CPU", 1)
    assert spans.engine_thread(pt) == engine
    assert {s.thread for s in by["ficabu.engine.step"]} == {engine}
    assert {s.thread for s in by["ficabu.drain.wait"]} == {worker}
    assert "bench.read" not in by            # the harness's own: xplane's
    assert [s.args["step_num"] for s in by["ficabu.engine.step"]] == [0, 1]
    assert by["ficabu.drain"][0].args == {"group": 0, "fire_step": 0,
                                          "payloads": "[1]"}
    (wait,) = by["ficabu.engine.publish_wait"]
    assert wait.args == {"group": 0, "fire_step": 0}
    assert wait.start == pytest.approx(1e-6 + 1.5e-6)


def test_leaf_ops_and_checkpoint_share(synthetic):
    pt, tr = synthetic
    (dev,) = pt.devices
    assert [o.name for o in dev.ops] == ["fusion.1", "fusion.3", "fusion.5",
                                         "fusion.6", "fusion.7"]
    pat = spans.scope_re("checkpoint")
    scoped = [o.name for o in dev.ops if pat.search(o.op_name)]
    assert scoped == ["fusion.5", "fusion.6", "fusion.7"]
    assert dict((o.name, o.op_name) for o in dev.ops)["fusion.5"] == REF
    # the values the sweep's own checkpoint_share gave, now any scope's
    assert spans.scoped_share(dev, "jit_sweep", "checkpoint") == (
        pytest.approx(7e-6), pytest.approx(10e-6), 1)
    assert spans.sweep_checkpoint_pct(pt) == pytest.approx(70.0)
    # the device planes agree with xplane.py's reduction of them
    assert tr.busy_s() == pytest.approx(12e-6)


def test_host_readings(synthetic):
    pt, _ = synthetic
    # step self times less admit and publish: 7 - 5.8 and 4 - 3 us
    assert spans.step_host_ms(pt) == pytest.approx(1.1e-3)
    assert spans.admit_host_ms(pt) == pytest.approx(3e-3)


def test_idle_gaps_named_down_to_the_engine_phase(synthetic):
    pt, tr = synthetic
    assert sorted(tr.idle_gaps()) == [
        ("bench.step_once", pytest.approx(4e-6)),
        ("bench.step_once", pytest.approx(4e-6))]
    got = dict(spans.idle_gaps(tr, pt))
    assert got == {
        "bench.step_once/ficabu.engine.publish_wait": pytest.approx(4e-6),
        "bench.step_once/ficabu.engine.step": pytest.approx(4e-6)}


def test_no_program_spans_read_nothing():
    """A trace of a program without spans or scopes (the older
    recording): every span reading is None, and the idle gaps keep
    xplane's names."""
    from jax.profiler import ProfileData
    text = cut_spans.load(RECORDED_NO_SPANS)
    pt = spans.reduce_xspace(spans.parse_text(text))
    tr = xplane.reduce_profile(ProfileData.from_text_proto(text))
    assert pt.spans == []
    assert spans.step_host_ms(pt) is None
    assert spans.admit_host_ms(pt) is None
    assert spans.sweep_checkpoint_pct(pt) is None
    assert spans.idle_gaps(tr, pt) == tr.idle_gaps()


def test_counter_readings():
    before = {"engine": {"steps": 10, "publications": 2,
                         "publish_waits": 1, "publish_wait_s": 0.25},
              "drain": {"groups": 3, "drain_s": 1.5, "sweep_wait_s": 0.5,
                        "arch": None, "engine": {"sweep_wait_s": 0.5}}}
    after = {"engine": {"steps": 110, "publications": 6,
                        "publish_waits": 4, "publish_wait_s": 0.65},
             "drain": {"groups": 7, "drain_s": 3.9, "sweep_wait_s": 1.3,
                       "arch": None, "engine": {"sweep_wait_s": 1.3}}}
    d = spans.counter_delta(before, after)
    assert d["engine"]["steps"] == 100
    assert d["drain"]["engine"]["sweep_wait_s"] == pytest.approx(0.8)
    assert "arch" not in d["drain"]
    assert spans.publish_wait_ms(d) == pytest.approx(0.4 / 4 * 1e3)
    assert spans.drain_host_ms(d) == pytest.approx((2.4 - 0.8) / 4 * 1e3)
    # nothing to read: a program without these counters, or no drains
    assert spans.publish_wait_ms({"engine": {}, "drain": {}}) is None
    assert spans.drain_host_ms(None) is None
    idle = spans.counter_delta(after, after)
    assert spans.publish_wait_ms(idle) is None
    assert spans.drain_host_ms(idle) is None


def test_cpu_profile_of_the_engine(tmp_path):
    """The engine and its drain worker under an active CPU profiler: the
    written trace holds the step and admission on one thread, the drain
    and its output wait on another, and the cut fixture keeps them."""
    import jax
    import system
    system._program()           # the program's ``src`` on the path
    from repro.api import ServeSpec
    from repro.data import synthetic as syn
    from repro.launch.serve import ForgetService, StreamEngine
    from repro.models import lm as LM
    P, G = 8, 4
    cfg = LM.LMConfig(name="spans-t", n_layers=2, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=64, vocab=64)
    toks, doms = syn.make_lm_domains(syn.LMDataConfig(
        vocab=64, n_domains=4, seq_len=P + G, n_per_domain=8, seed=0))
    svc = ForgetService(cfg, toks, doms, P + G,
                        serve=ServeSpec(chunk_size=4))
    svc.submit(1, due_batch=1)
    eng = StreamEngine(LM.init_lm(jax.random.PRNGKey(0), cfg), cfg,
                       gen_len=G, prompt_len=P, max_batch=4, admit_chunk=2,
                       publish_lag=1, service=svc)
    for i in range(3):
        eng.enqueue(i, np.asarray(toks[i, :P]))
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    pt = spans.load(str(tmp_path))
    threads = {}
    for s in pt.spans:
        threads.setdefault(s.name, set()).add(s.thread)
    engine = spans.engine_thread(pt)
    for name in ("ficabu.engine.step", "ficabu.engine.admit",
                 "ficabu.engine.decode", "ficabu.engine.fire"):
        assert threads[name] == {engine}, name
    (worker,) = threads["ficabu.drain"]
    assert worker != engine
    for name in ("ficabu.drain.prepare", "ficabu.drain.sweep",
                 "ficabu.drain.wait", "ficabu.drain.finish"):
        assert threads[name] == {worker}, name
    assert len([s for s in pt.spans if s.name == "ficabu.engine.step"]) \
        == eng.step
    assert spans.admit_host_ms(pt) > 0 and spans.step_host_ms(pt) > 0
    # the fixture cut keeps every span from 20 ms before the drain on,
    # arguments and all
    with open(xplane.find_xplane(str(tmp_path)), "rb") as f:
        space = spans.parse(f.read())
    cut = spans.reduce_xspace(spans.parse_text(cut_spans.cut(space,
                                                             ms=1e6)))
    lo = min(s.start for s in pt.spans if s.name == "ficabu.drain") - 0.02
    assert [(s.name, s.args) for s in cut.spans] == [
        (s.name, s.args) for s in pt.spans if s.start >= lo]


RECORDED_NO_SPANS = os.path.join(HERE, "data",
                             "internvl2-1b-lm.chat-forget.txtpb.gz")
NO_SPANS = {
    "decode_step_ms": 2.237660074074094, "prefill_ms": 18.89724900000056,
    "busy_s": 0.08001285699998206, "idle_gaps_s": 0.001082681999999835,
    "device_ops": [
        "jit__step:%while.1", "jit__step:%bitcast_add_fusion.5",
        "jit_prefill_block:%while.2", "jit__step:%bitcast_reduce_fusion",
        "jit__step:%fusion.192", "jit_prefill_block:%fusion.205",
        "jit_prefill_block:%convolution_bitcast_fusion",
        "jit_prefill_block:%fusion.204", "jit__step:%copy.81",
        "jit__step:%copy.78"],
    "idle_gaps": ["host.other", "bench.read"] + ["host.other"] * 8}


def test_older_recording_reads_as_before():
    """``xplane.py`` and the decode and admission readers on the older
    recording, pinned to the numbers they gave when it was recorded."""
    import cut_trace
    tr = xplane.reduce_profile(cut_trace.load(RECORDED_NO_SPANS))
    reg = Registry()
    view = harness.RunView(None, None, None, None, [], 0, tr, None, None,
                           None)
    assert reg.metric_reader("decode_step_ms")(view) == pytest.approx(
        NO_SPANS["decode_step_ms"], rel=1e-12)
    assert reg.metric_reader("prefill_ms")(view) == pytest.approx(
        NO_SPANS["prefill_ms"], rel=1e-12)
    assert tr.busy_s() == pytest.approx(NO_SPANS["busy_s"], rel=1e-12)
    bd = xplane.breakdown(tr)
    assert [k for k, _ in bd["device_ops"]] == NO_SPANS["device_ops"]
    assert [k for k, _ in bd["idle_gaps"]] == NO_SPANS["idle_gaps"]
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        NO_SPANS["idle_gaps_s"], rel=1e-12)


RECORDED = os.path.join(HERE, "data",
                        "internvl2-1b-lm.chat-forget.spans.txtpb.gz")
COUNTERS = os.path.join(HERE, "data",
                        "internvl2-1b-lm.chat-forget.counters.json")


def test_recorded_tpu_spans_pin_the_five_readings():
    """A 1 s cut of a traced run of ``internvl2-1b-lm.chat-forget`` on a
    TPU v5 lite (``cut_spans.py``, from 20 ms before a drain): the drain
    and its sweep, the publication that joins it, three admissions; and
    the counters of that run's whole window.  The five readings are
    pinned, and the longest idle gaps lie in the publication's join."""
    from jax.profiler import ProfileData
    text = cut_spans.load(RECORDED)
    pt = spans.reduce_xspace(spans.parse_text(text))
    tr = xplane.reduce_profile(ProfileData.from_text_proto(text))
    with open(COUNTERS) as f:
        counters = json.load(f)
    assert spans.step_host_ms(pt) == pytest.approx(0.8903999999999579)
    assert spans.admit_host_ms(pt) == pytest.approx(134.74878099999998)
    assert spans.sweep_checkpoint_pct(pt) == pytest.approx(56.08910869515768)
    assert spans.publish_wait_ms(counters) == pytest.approx(
        348.73661192156703)
    assert spans.drain_host_ms(counters) == pytest.approx(
        498.80905472549085)
    (drain,) = [s for s in pt.spans if s.name == "ficabu.drain"]
    (wait,) = [s for s in pt.spans
               if s.name == "ficabu.engine.publish_wait"]
    assert drain.thread != wait.thread == spans.engine_thread(pt)
    assert wait.args["fire_step"] == drain.args["fire_step"]
    gaps = spans.idle_gaps(tr, pt)
    assert [n for n, _ in gaps[1:]] == (
        ["bench.step_once/ficabu.engine.publish_wait"] * 9)
    # the device reading of xplane.py is the same on this cut
    assert tr.program_seconds()["jit_sweep"][1] == 1


def test_scoped_share_reads_as_checkpoint_share_did():
    """On the recorded TPU cut, ``scoped_share`` of the sweep's
    ``checkpoint`` scope gives, digit for digit, the (scoped, sweep)
    seconds the sweep-only ``checkpoint_share`` gave; the decode step holds
    no such op, and a program with no launch reads None."""
    pt = spans.reduce_xspace(spans.parse_text(cut_spans.load(RECORDED)))
    (dev,) = pt.devices
    assert spans.scoped_share(dev, "jit_sweep", "checkpoint") == (
        0.08591045382798171, 0.1531678, 1)
    got = spans.scoped_share(dev, "jit__step", "checkpoint")
    assert got[0] == 0.0 and got[2] == 23
    assert spans.scoped_share(dev, "jit_no_such_program", "checkpoint") \
        is None
    assert spans.scoped(pt, "jit_sweep", "checkpoint") == \
        spans.scoped_share(dev, "jit_sweep", "checkpoint")


READINGS = ("step_host_ms", "admit_host_ms", "publish_wait_ms",
            "drain_host_ms", "sweep_checkpoint_pct")


def test_the_five_readers_read_the_recorded_tpu_spans():
    """The five per-layer metrics of the program's spans, scopes and
    counters, read through a ``RunView`` as the harness builds it, give
    the values ``test_recorded_tpu_spans_pin_the_five_readings`` pins; an
    untraced view with no counters reads None in each."""
    from jax.profiler import ProfileData
    text = cut_spans.load(RECORDED)
    pt = spans.reduce_xspace(spans.parse_text(text))
    tr = xplane.reduce_profile(ProfileData.from_text_proto(text))
    with open(COUNTERS) as f:
        counters = json.load(f)
    reg = Registry()
    view = harness.RunView(None, None, None, None, [], 0, tr, None, pt,
                           counters)
    got = {m: reg.metric_reader(m)(view) for m in READINGS}
    assert got == {"step_host_ms": pytest.approx(0.8903999999999579),
                   "admit_host_ms": pytest.approx(134.74878099999998),
                   "publish_wait_ms": pytest.approx(348.73661192156703),
                   "drain_host_ms": pytest.approx(498.80905472549085),
                   "sweep_checkpoint_pct": pytest.approx(56.08910869515768)}
    assert view.scoped("jit_sweep", "checkpoint") == (
        0.08591045382798171, 0.1531678, 1)
    empty = harness.RunView(None, None, None, None, [], 0, None, None, None,
                            {})
    assert {m: reg.metric_reader(m)(empty) for m in READINGS} == dict.fromkeys(
        READINGS)
