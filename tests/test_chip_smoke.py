"""``chip_smoke.py`` rehearsed on the CPU: its one-chip phases at SMOKE
size with interpret-mode Pallas, its four-chip phases on 4 virtual CPU
devices, and its refusal to run anywhere but a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.surf_paper import SMOKE

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _records(stdout):
    return {r["phase"]: r for r in map(json.loads, stdout.splitlines())
            if "phase" in r}


def _within_tol(r):
    tol = r["tol"]
    return r["max_dloss"] <= tol["loss_atol"] and r["max_dacc"] == 0.0


def test_chip_smoke_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_one_chip_phases_at_smoke_size(chip_smoke, capsys):
    chip_smoke.run_one_chip(SMOKE)
    recs = _records(capsys.readouterr().out)
    assert list(recs) == ["sample_rows", "train_dense", "train_pallas",
                          "evaluate", "serve"]
    assert recs["sample_rows"]["exact"] is True
    assert recs["sample_rows"]["shape"] == [
        SMOKE.n_layers, SMOKE.n_agents, SMOKE.batch_per_agent,
        SMOKE.feature_dim]
    assert len(recs["train_dense"]["test_loss"]) == chip_smoke.STEPS
    # on the CPU the kernel runs in the interpreter, so parity is tight
    assert recs["train_pallas"]["mixer"]["interpret"] is True
    assert _within_tol(recs["train_pallas"]) and _within_tol(recs["serve"])
    assert recs["serve"]["requests"] == chip_smoke.N_REQUESTS
    assert recs["evaluate"]["federations"] == chip_smoke.N_HELD


def test_chip_smoke_four_chip_phases_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import chip_smoke\n"
            "from repro.configs.surf_paper import SMOKE\n"
            "chip_smoke.run_four_chips(SMOKE)\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = _records(proc.stdout)
    assert list(recs) == ["train_dense_1dev", "train_halo_4dev",
                          "serve_4dev"]
    assert recs["train_dense_1dev"]["devices"] == [0]
    assert recs["train_halo_4dev"]["devices"] == [0, 1, 2, 3]
    assert recs["train_halo_4dev"]["agents_per_device"] == \
        SMOKE.n_agents // 4
    assert _within_tol(recs["train_halo_4dev"])
    assert _within_tol(recs["serve_4dev"])
    assert recs["serve_4dev"]["output_devices"] == [0, 1, 2, 3]
    assert recs["serve_4dev"]["output_shard"] == [2]    # 8 requests / 4
