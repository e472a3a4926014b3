"""The reduction from a profiler trace to busy time, idle share, top
device operations and idle gaps charged to the benchmark's host spans."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import trace_reduce  # noqa: E402

MS = 1_000_000


def test_union_and_gaps():
    merged = trace_reduce.union([(5, 9), (0, 2), (1, 3), (8, 12)], 0, 10)
    assert merged == [[0, 3], [5, 10]]
    assert trace_reduce.gaps(merged, 0, 12) == [(3, 5), (10, 12)]


def test_hand_made_trace():
    # window 0..100 ms; a while op 10-40 holds fusion.1 10-25 and fusion.2
    # 25-35; copy.3 runs 60-70; an op before the window is clipped away.
    # Gaps 0-10 (span "wait"), 40-60 (dispatch 40-55, then nothing) and
    # 70-100 (no span at all)
    rec = {"device_ops": [[["%while.1", 10 * MS, 30 * MS],
                           ["%fusion.1", 10 * MS, 15 * MS],
                           ["%fusion.2", 25 * MS, 10 * MS],
                           ["%copy.3", 60 * MS, 10 * MS],
                           ["%fusion.1", -5 * MS, 3 * MS]]],
           "host_spans": [["window", 0, 100 * MS],
                          ["wait", 0, 10 * MS],
                          ["dispatch", 40 * MS, 15 * MS]]}
    red = trace_reduce.reduce_trace(rec)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.04)
    assert red["idle_share"] == pytest.approx(0.6)
    assert red["device_ops"][0] == ["%fusion.1", pytest.approx(0.015)]
    assert dict(red["device_ops"]) == {
        "%fusion.1": pytest.approx(0.015), "%fusion.2": pytest.approx(0.01),
        "%copy.3": pytest.approx(0.01), "%while.1": pytest.approx(0.005)}
    assert dict(red["idle_gaps"]) == {
        trace_reduce.NO_SPAN: pytest.approx(0.03),
        "dispatch": pytest.approx(0.02), "wait": pytest.approx(0.01)}


def test_names_are_cut_to_the_instruction():
    assert trace_reduce.op_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == \
        "%fusion.12"


def test_recorded_chip_trace():
    """40 ms of a traced train-paper-dfl window on one v5e: busy time
    against a count on a 1 µs grid, self times adding up to it, and every
    idle nanosecond charged to some span or to none."""
    with open(os.path.join(BENCH, "tests", "data",
                           "trace_train_paper.json")) as f:
        rec = json.load(f)
    red = trace_reduce.reduce_trace(rec)
    lo, dur = next((s, d) for n, s, d in rec["host_spans"] if n == "window")
    grid = np.zeros(int(dur // 1000), bool)
    for _, s, d in rec["device_ops"][0]:
        a, b = max(0, int((s - lo) // 1000)), int((s + d - lo) // 1000)
        grid[a:max(a, b)] = True
    assert red["window_s"] == pytest.approx(0.04)
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=2e-5)
    assert 0.0 < red["idle_share"] < 0.05
    ranked = trace_reduce.reduce_trace(rec, top=10 ** 6)
    assert sum(t for _, t in ranked["device_ops"]) == \
        pytest.approx(red["busy_s"], rel=1e-9)
    assert sum(t for _, t in ranked["idle_gaps"]) == \
        pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert len(red["device_ops"]) == 10
    assert all(name.startswith("%") and " " not in name
               for name, _ in red["device_ops"])
    assert {n for n, _ in red["idle_gaps"]} <= {
        "dispatch", "wait", trace_reduce.NO_SPAN}


def test_two_chips_are_averaged():
    one = [["op", 0, 50 * MS]]
    rec = {"device_ops": [one, []], "host_spans": [["window", 0, 100 * MS]]}
    red = trace_reduce.reduce_trace(rec)
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["idle_share"] == pytest.approx(0.75)


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_trace({"device_ops": [[]], "host_spans": []})
