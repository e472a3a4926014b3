"""The harness finds every file of a cell by name and refuses a missing
one; BENCHMARK.json and the files under bench/ agree."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_is_found_by_name(cell):
    c = harness.load_cell(cell)
    entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c["config"] == entry["config"] and c["chips"] == entry["chips"]
    assert callable(harness.load_module("jobs", c["job"]).run)
    assert harness.load_module("traffic", c["traffic"]["kind"])
    assert c["limits"] and all(v >= 0 for v in c["limits"].values())
    cfg_entry = next(k for k in SPEC["configs"] if k["name"] == c["config"])
    assert cfg_entry["file"] == f"bench/configs/{c['config']}.json"
    with open(os.path.join(harness.ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == set(cfg_entry["reduced"])
    assert cfg["source"] == cfg_entry["source"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_each_per_layer_metric_has_a_reader(metric):
    reader = harness.load_module("metrics", metric)
    assert reader.read({}) is None          # nothing to read: no number


def test_missing_files_are_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such.metric")
    with pytest.raises(harness.BenchError):
        harness.load_module("jobs", "no_such_job")
    with pytest.raises(harness.BenchError):
        harness.peaks_for("no such device")


def test_peaks_table_is_keyed_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_spec_names_and_references():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
        assert 0.01 <= m["bound"] <= 0.25


def test_spec_keys_units_and_lines():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    lines = ([w["why"] for w in SPEC["workloads"] + SPEC["configs"]]
             + [m["layer"] for m in SPEC["per_layer"]]
             + [c["source"] for c in SPEC["configs"]] + SPEC["command"])
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
               for s in lines)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert harness.load_cell(w["name"])["traffic"]["kind"] == w["traffic"]


def test_no_tpu_means_no_result():
    """On a machine without a TPU the harness refuses before any run."""
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.require_devices(1)
