"""``download_mb.serve`` on synthetic records: the bytes the ticks inside
the window brought to the host over the requests they answered, and no
number without spans or without a ``bytes_out`` counter."""
import os
import sys
from typing import NamedTuple

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import program_spans as ps  # noqa: E402

READER = harness.load_module("metrics", "download_mb.serve")


class Rec(NamedTuple):
    name: str
    parent: str | None
    thread: int
    t0: float
    t1: float
    cpu_s: float
    attrs: dict


def _ctx(lo=10.0, hi=20.0):
    spans = harness.Spans()
    spans.records.append(("window", lo, hi))
    return {"spans": spans}


def _tick(t, reqs, **unpack):
    """One tick at ``t`` answering ``reqs``; ``unpack``: its unpack span's
    attributes."""
    return [Rec("serve.tick", None, 7, t, t + 0.3, 0.1,
                {"reqs": list(reqs), "bucket": (128, 16)}),
            Rec("serve.tick.unpack", "serve.tick", 7, t + 0.2, t + 0.25,
                0.05, dict(unpack))]


@pytest.mark.parametrize("ticks,want", [
    ([(12.0, [0], 3_000_000)], 3.0),
    ([(12.0, [0, 1], 5_000_000), (14.0, [2, 3, 4], 7_000_000)], 12.0 / 5),
    # the second tick starts after the window: not counted
    ([(12.0, [0, 1], 5_000_000), (30.0, [2], 9_000_000)], 2.5),
])
def test_reads_bytes_over_answered_requests(ticks, want, monkeypatch):
    recs = [r for t, reqs, b in ticks for r in _tick(t, reqs, bytes_out=b)]
    monkeypatch.setattr(ps, "program_records", lambda: recs)
    assert READER.read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("recs", [
    [],
    _tick(12.0, [0, 1]),                  # unpack without bytes_out
    [Rec("serve.tick", None, 7, 12.0, 12.001, 0.0, {"reqs": []})],
], ids=["no_spans", "no_counter", "empty_queue"])
def test_no_number_without_a_count(recs, monkeypatch):
    monkeypatch.setattr(ps, "program_records", lambda: recs)
    assert READER.read(_ctx()) is None


def test_no_number_without_the_window(monkeypatch):
    monkeypatch.setattr(ps, "program_records",
                        lambda: _tick(12.0, [0], bytes_out=1_000))
    assert READER.read({}) is None
