"""The four-chip serving cells' readers on synthetic records: the θ
roofline divides the θ bytes of the solver's runs on a chip by their
device time and one chip's HBM rate, the gather reader averages the
``gather_bytes`` counter over calls, the mesh MFU divides by every chip's
peak, the job clips the solver's device runs to the window, and a program
without the counters (the parent) or a run without a profile gives no
number."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import program_spans as ps  # noqa: E402
from test_bench_program_spans import Rec, _ctx  # noqa: E402

PEAKS = harness.peaks_for("TPU v5 lite")


def _calls(t, theta_bytes, gather_bytes, call_s=0.01, wait_s=0.04):
    attrs = {"bytes_in": 40, "devices": 4}
    if theta_bytes is not None:
        attrs.update(theta_bytes=theta_bytes, gather_bytes=gather_bytes)
    return [Rec("serve.tick.call", "serve.tick", 7, t, t + call_s, 0.0,
                attrs),
            Rec("serve.tick.wait", "serve.tick", 7, t + call_s,
                t + call_s + wait_s, 0.0, {})]


def _reader(name, ctx, recs, monkeypatch):
    monkeypatch.setattr(ps, "program_records", lambda: recs)
    return harness.load_module("metrics", name).read(ctx)


def test_theta_roofline_is_bytes_over_call_seconds(monkeypatch):
    recs = (_calls(12.0, 2e9, 0) + _calls(13.0, 2e9, 0, wait_s=0.09)
            + _calls(30.0, 9e9, 0))                       # outside
    ctx = dict(_ctx(), peaks=PEAKS, solver_device_s=0.05, solver_runs=2)
    value = _reader("theta_roofline.serve_mesh", ctx, recs, monkeypatch)
    assert value == pytest.approx(100 * 4e9 / 0.05 / PEAKS["hbm_bytes_per_s"])


def test_theta_roofline_needs_the_profile(monkeypatch):
    ctx = dict(_ctx(), peaks=PEAKS, solver_device_s=None, solver_runs=0)
    assert _reader("theta_roofline.serve_mesh", ctx, _calls(12.0, 2e9, 0),
                   monkeypatch) is None


def test_gather_mb_is_the_mean_a_call(monkeypatch):
    recs = _calls(12.0, 1e9, 1e9) + _calls(13.0, 1e9, 2e9)
    value = _reader("gather_mb.serve_mesh", _ctx(), recs, monkeypatch)
    assert value == pytest.approx(1500.0)


@pytest.mark.parametrize("name", ["theta_roofline.serve_mesh",
                                  "gather_mb.serve_mesh"])
def test_no_counters_no_number(name, monkeypatch):
    ctx = dict(_ctx(), peaks=PEAKS)
    assert _reader(name, ctx, _calls(12.0, None, None), monkeypatch) is None
    assert _reader(name, ctx, [], monkeypatch) is None


def test_mesh_mfu_divides_by_every_chip():
    ctx = {"request_flops": 2e12, "solved": 100, "solver_device_s": 10.0,
           "devices": 4, "peaks": PEAKS}
    value = harness.load_module("metrics", "mfu.serve_mesh").read(ctx)
    assert value == pytest.approx(100 * 2e13 / (4 * PEAKS["bf16_flops"]))
    for gone in ({"devices": None}, {"solver_device_s": None}):
        assert harness.load_module("metrics", "mfu.serve_mesh").read(
            dict(ctx, **gone)) is None


def test_solver_runs_are_clipped_to_the_window():
    job = harness.load_module("jobs", "serve_mesh")
    chips = [[(0, 40), (90, 130), (200, 260)],         # 10 + 40 + 0
             [(95, 125), (150, 170)]]                  # 30 + 20
    seconds, runs = job.runs_inside(chips, 30, 180)
    assert seconds == pytest.approx(100e-9 / 2)
    assert runs == 2.0
    assert job.runs_inside(chips, 300, 400) == (None, 0)
