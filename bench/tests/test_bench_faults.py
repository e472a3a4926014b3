"""Runs of each cell's job at a smoke size on the CPU, past the harness's
look for a chip: sound, ``correct`` comes out true; with the timed path
broken underneath, or with the control (the reference in bfloat16) in
the program's place, it comes out false."""
import os
import sys

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))

SMOKE = {"n_agents": 8, "n_layers": 3, "feature_dim": 16, "n_classes": 4,
         "batch_per_agent": 4, "train_per_agent": 9, "test_per_agent": 6}
SEED = 2 ** 40 + 12345


def smoke_cell(name, **traffic):
    cell = harness.load_cell(name)
    cell["cfg"].update(SMOKE)
    cell["traffic"].update(traffic)
    return cell


def train_cell():
    return smoke_cell("train-paper-dfl", pool=5, steps_per_chunk=2)


def serve_cell():
    return smoke_cell("serve-paper-dfl-poisson", rate=20.0, federations=5,
                      compare=30)


def run(cell, fault=None):
    job = harness.load_module("jobs", cell["job"])
    return job.run(cell, SEED, 0.5, False, jax.devices(), fault=fault)


@pytest.mark.parametrize("make", [train_cell, serve_cell])
def test_sound_run_is_correct(make):
    r = run(make())
    assert harness.verdict(r["checks"]), r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_mix"])
def test_broken_training_is_not_correct(fault):
    r = run(train_cell(), fault)
    assert not harness.verdict(r["checks"]), r["checks"]


def test_unconstrained_training_reads_a_whole_dual_gap():
    """The duals after the first step follow the reference in a sound run
    and read a gap of 1 when λ is held at 0."""
    sound = run(train_cell())["info"]["gaps"]
    broken = run(train_cell(), "no_dual")["info"]["gaps"]
    assert sound["lam_gap"] < 1e-3 and broken["lam_gap"] == 1.0


@pytest.mark.parametrize("fault", ["altered", "half_batch", "no_mix"])
def test_broken_serving_is_not_correct(fault):
    r = run(serve_cell(), fault)
    assert not harness.verdict(r["checks"]), r["checks"]


def test_training_control_is_not_correct():
    cell = train_cell()
    rows = list(harness.load_module("jobs", "train").calibrate(
        cell, SEED, jax.devices(), with_program=False))
    gaps = rows[-1]
    assert gaps["kind"] == "control"
    assert any(gaps[k] > lim for k, lim in cell["limits"].items()), gaps


def test_serving_control_is_not_correct():
    cell = serve_cell()
    rows = harness.load_module("jobs", "serve").calibrate(
        cell, SEED, jax.devices(), with_program=False)
    gaps = rows[-1]
    assert gaps["kind"] == "control"
    assert any(gaps[k] > lim for k, lim in cell["limits"].items()), gaps
