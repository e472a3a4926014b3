"""Rules of ``BENCHMARK.json`` that a manifest must keep to be run at all:
each (config, traffic) pair names one cell, and at most half the cells
(rounded down, and at least one) ask for four chips."""
import json
from collections import Counter
from pathlib import Path

import pytest

SPEC = json.loads((Path(__file__).resolve().parents[1]
                   / "BENCHMARK.json").read_text())
CELLS = SPEC["workloads"]


def test_each_config_and_traffic_pair_is_given_once():
    pairs = Counter((w["config"], w["traffic"]) for w in CELLS)
    assert [p for p, n in pairs.items() if n > 1] == []


def test_at_most_half_the_cells_take_four_chips():
    four = [w["name"] for w in CELLS if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2), four


@pytest.mark.parametrize("cell", [w["name"] for w in CELLS])
def test_each_cell_names_a_listed_config(cell):
    entry = next(w for w in CELLS if w["name"] == cell)
    assert entry["config"] in {c["name"] for c in SPEC["configs"]}
    assert entry["chips"] in (1, 4)
