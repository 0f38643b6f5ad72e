"""Every cell, configuration, mix and metric of BENCHMARK.json is found by
name, and a new cell is taken by adding its file alone."""
import json
import os
import sys

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
