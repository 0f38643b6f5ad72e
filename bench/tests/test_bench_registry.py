"""Every cell, configuration, mix and metric of BENCHMARK.json is found by
name, and a new cell, a new model family, or a new per-layer metric that
reads a new named scope and counter, is taken by adding its files alone."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tiny  # noqa: E402
from registry import ROOT, Registry  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found(cell):
    reg = Registry()
    c = reg.cell(cell)
    assert c["name"] == cell
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c["config"] == entry["config"]
    assert c["traffic"] == entry["traffic"]
    reg.config(c["config"])
    reg.mix(c["traffic"])
    assert reg.chips(cell) == entry["chips"]


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_every_config_is_found(cfg):
    c = Registry().config(cfg["name"])
    assert c["name"] == cfg["name"]
    assert os.path.normpath(cfg["file"]) == os.path.join(
        "bench", "configs", cfg["name"] + ".json")
    for key in cfg["reduced"]:
        assert key in c and key in c["reduced"], key


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(Registry().metric_reader(metric))


def test_metrics_apply_only_to_listed_cells():
    reg = Registry()
    for w in BENCH["workloads"]:
        names = [m["name"] for m in reg.end_to_end(w["name"])]
        assert "setup_s" in names and len(names) >= 2
        assert reg.per_layer(w["name"])
        forget = float(reg.cell(w["name"]).get("forget_rate", 0)) > 0
        assert ("forget_p90_s" in names) == forget


def test_a_new_cell_is_taken_by_adding_its_file(tmp_path):
    before = {}
    for dirpath, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = os.path.getmtime(p)
    root = tiny.make_root(str(tmp_path))
    reg = Registry(root)
    assert "tiny.chat-forget" in reg.cells()
    assert reg.cell("tiny.chat-forget")["config"] == "tiny"
    assert reg.config("tiny")["hidden_size"] == 64
    # the new cell is one more file; no file of the benchmark changed
    for p, t in before.items():
        assert os.path.getmtime(p) == t
    assert set(reg.cells()) == set(Registry().cells()) | {"tiny.chat-forget"}


def test_unknown_names_are_errors():
    reg = Registry()
    with pytest.raises(KeyError):
        reg.cell("no-such-cell")
    with pytest.raises(KeyError):
        reg.metric_reader("no_such_metric")


# A test-only family: the dense math, with block 0 stored apart from blocks
# 1..L-1 (two segments), the layout a dense block in front of expert blocks
# needs.  It re-nests into the program's one-kind ``period_stack``.
DENSE_SPLIT = '''
import importlib.util
import os

import jax.numpy as jnp

import reference as R

_spec = importlib.util.spec_from_file_location(
    "dense_for_split", os.path.join(os.path.dirname(__file__), "dense.py"))
D = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(D)

MODEL_TYPES = ("dense-split-test",)
FIRST = "first."
Shape, HEAD_LEAVES, head, init_cache = D.Shape, D.HEAD_LEAVES, D.head, \\
    D.init_cache
lm_config = D.lm_config
decode_step_flops, decode_step_bytes = D.decode_step_flops, \\
    D.decode_step_bytes
forward_flops, drain_flops = D.forward_flops, D.drain_flops


def split(w):
    out = {}
    for k, a in w.items():
        if k in D.BLOCK_LEAVES:
            out[FIRST + k], out[k] = a[:1], a[1:]
        else:
            out[k] = a
    return out


def join(w):
    out = {k: a for k, a in w.items() if not k.startswith(FIRST)}
    for k in D.BLOCK_LEAVES:
        if k in w:
            out[k] = jnp.concatenate([w[FIRST + k], w[k]])
    return out


def shapes(cfg):
    out = {}
    for k, s in D.shapes(cfg).items():
        if k in D.BLOCK_LEAVES:
            out[FIRST + k], out[k] = (1,) + s[1:], (s[0] - 1,) + s[1:]
        else:
            out[k] = s
    return out


def init(key, cfg):
    return split(D.init(key, cfg))


def program_tree(w):
    return D.program_tree(join(w))


def neutral_tree(p):
    return split(D.neutral_tree(p))


def segments(sh):
    (seg,) = D.segments(sh)
    return [R.Segment(D.block, {k: FIRST + k for k in seg.leaves}, 1),
            R.Segment(D.block, dict(seg.leaves), sh.L - 1)]


def segment_logits(w, tokens, sh, kv, done, seg, quant=False):
    return D.segment_logits(join(w), tokens, sh, kv, done, seg, quant)
'''


def _tree_digest(root):
    import hashlib
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_family_is_taken_by_adding_its_file(tmp_path,
                                                  restore_jax_cache):
    import jax.numpy as jnp
    import harness
    import reference as R
    import weights as Wt
    root = tiny.make_root(str(tmp_path))
    before = _tree_digest(root)
    b = os.path.join(root, "bench")
    cfg = dict(tiny.TINY_CONFIG, name="tiny-split",
               model_type="dense-split-test")
    added = {os.path.join("bench", "families", "dense_split.py"): DENSE_SPLIT,
             os.path.join("bench", "configs", "tiny-split.json"):
                 json.dumps(cfg),
             os.path.join("bench", "workloads", "tiny-split.chat-forget.json"):
                 json.dumps(dict(tiny.tiny_cell("tiny-split.chat-forget"),
                                 config="tiny-split"))}
    for rel, text in added.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)

    # the CPU harness end to end, as test_sound_run_is_correct runs it
    out = harness.run("tiny-split.chat-forget", 5, 3.0, False, root=root,
                      require_tpu=False, hooks={"peak": tiny.CPU_PEAK})
    assert out["correct"], out["checks"]
    assert out["checks"]["drain_mismatch"]["value"] == 0

    # the same weights in the two layouts: the same Fisher and drain
    reg = Registry(root)
    split, dense = reg.family(cfg), reg.family(tiny.TINY_CONFIG)
    assert split is not dense
    w = Wt.make_weights(dense, tiny.TINY_CONFIG, 11)
    ws = Wt.make_weights(split, cfg, 11)
    assert split.join(ws).keys() == w.keys()
    for k in w:
        assert (np.asarray(split.join(ws)[k]) == np.asarray(w[k])).all(), k
    sh = dense.Shape(tiny.TINY_CONFIG)
    tokens, labels = Wt.make_domains(tiny.TINY_CONFIG, tiny.TINY_MIX, 11)
    retain, rows = jnp.asarray(tokens[:32]), jnp.asarray(
        tokens[labels == 2][:8])
    unl = dict(tiny.UNLEARN, tau=-1.0)
    got, want = {}, {}
    for fam, weights, res in ((split, ws, got), (dense, w, want)):
        res["fisher"] = R.global_fisher(fam, weights, retain, sh, 4, 1e-4)
        res["drain"], res["stop"] = R.drain(fam, weights, res["fisher"],
                                            rows, sh, unl)
    assert got["stop"] == want["stop"]
    for part in ("fisher", "drain"):
        joined = split.join(got[part])
        assert joined.keys() == want[part].keys()
        for k, a in want[part].items():
            assert (np.asarray(joined[k]) == np.asarray(a)).all(), (part, k)

    # an unknown model_type fails at set-up, naming it
    with open(os.path.join(b, "configs", "tiny-split.json"), "w") as f:
        json.dump(dict(cfg, model_type="no-such-family"), f)
    with pytest.raises(KeyError, match="no-such-family") as e:
        harness.setup("tiny-split.chat-forget", 5, 1.0, root=root,
                      require_tpu=False, hooks={"peak": tiny.CPU_PEAK})
    assert "dense_split.py" in str(e.value) and "dense.py" in str(e.value)

    # no file of the tree was changed to admit the family: three were added
    after = _tree_digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(added)


# A test-only per-layer metric of a kind no program has yet: device time in
# an ``experts`` named scope of the decode step, per routed token from a
# counter the engine does not keep today.
EXPERTS_METRIC = '''
"""Test only: decode-step device microseconds in the ``experts`` scope per
routed token."""


def read(run):
    got = run.scoped("jit__step", "experts")
    tokens = run.counters.get("engine", {}).get("routed_tokens")
    if got is None or not tokens:
        return None
    return got[0] / tokens * 1e6
'''


def _op(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _op_meta(mid, name, op_name=None):
    st = ("" if op_name is None else
          f'stats {{ metadata_id: 1 str_value: {json.dumps(op_name)} }}')
    return (f"event_metadata {{ key: {mid} value {{ id: {mid} "
            f"name: {json.dumps(name)} {st} }} }}")


# jit__step launches [0, 4) and [10, 14) us; jit_sweep [5, 8).  In the
# steps, ``experts`` ops take [0, 1), [3, 4) and [10, 12); an ``attn`` op
# and an ``experts_gate`` op (another scope) do not count, nor does the
# sweep's own ``experts`` op.
EXPERTS_XSPACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    {_op(11, 0, 4)} {_op(12, 5, 3)} {_op(11, 10, 4)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {_op(21, 0, 1)} {_op(22, 1, 2)} {_op(23, 3, 1)} {_op(24, 5, 2)}
    {_op(21, 10, 2)} {_op(25, 12, 2)} }}
  {_op_meta(11, "jit__step(7)")}
  {_op_meta(12, "jit_sweep(2)")}
  {_op_meta(21, "fusion.1", "jit(_step)/layers/experts/dot_general:")}
  {_op_meta(22, "fusion.2", "jit(_step)/layers/attn/dot_general:")}
  {_op_meta(23, "fusion.3", "jit(_step)/experts/add:")}
  {_op_meta(24, "fusion.4", "jit(sweep)/experts/dot_general:")}
  {_op_meta(25, "fusion.5", "jit(_step)/experts_gate/mul:")}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
"""


def test_a_new_metric_reads_a_new_scope_and_counter_by_adding_its_file(
        tmp_path):
    """A metric that reads a named scope and a counter key no program has
    today is one new reader file and one ``per_layer`` entry: the
    harness's ``RunView`` hands it the scoped device time and the counter,
    and no file of ``bench/`` is edited."""
    import harness
    import spans
    root = tiny.make_root(str(tmp_path))
    before = _tree_digest(root)
    rel = os.path.join("bench", "metrics", "experts_us_per_token.py")
    with open(os.path.join(root, rel), "w") as f:
        f.write(EXPERTS_METRIC)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["per_layer"].append({
        "name": "experts_us_per_token", "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "experts",
        "moves": "tpot_mean_ms", "workloads": ["tiny.chat-forget"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    reg = Registry(root)
    assert "experts_us_per_token" in [
        m["name"] for m in reg.per_layer("tiny.chat-forget")]
    pt = spans.reduce_xspace(spans.parse_text(EXPERTS_XSPACE))
    # a key the engine's stats() may add later reaches the reader through
    # the harness's window delta
    counters = spans.counter_delta(
        {"engine": {"steps": 3, "routed_tokens": 100}, "drain": {}},
        {"engine": {"steps": 5, "routed_tokens": 150}, "drain": {}})
    view = harness.RunView(None, None, None, None, [], 0, None, None, pt,
                           counters)
    assert view.scoped("jit__step", "experts") == (
        pytest.approx(4e-6), pytest.approx(8e-6), 2)
    assert view.scoped("jit_sweep", "experts") == (
        pytest.approx(2e-6), pytest.approx(3e-6), 1)
    assert view.scoped("jit__admit", "experts") is None
    read = reg.metric_reader("experts_us_per_token")
    assert read(view) == pytest.approx(4e-6 / 50 * 1e6)
    # nothing to read: untraced, or a program without the counter
    assert read(harness.RunView(None, None, None, None, [], 0, None, None,
                                None, counters)) is None
    assert read(harness.RunView(None, None, None, None, [], 0, None, None,
                                pt, {"engine": {"steps": 2}})) is None

    # one file added under bench/, none changed
    after = _tree_digest(root)
    assert set(after) - set(before) == {rel}
    del before["BENCHMARK.json"]
    assert {k: after[k] for k in before} == before
