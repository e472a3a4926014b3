"""The readers of the program's spans on synthetic records: only records
that start inside the window count, a request's queue wait joins its
``submit`` to the tick that admitted it by id, and no records give no
number."""
import os
import sys
from typing import NamedTuple

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
import harness  # noqa: E402
import program_spans as ps  # noqa: E402

SERVE_READERS = ("featurize_ms.serve", "queue_wait_p95_ms.serve",
                 "tick_stack_ms.serve", "tick_unpack_ms.serve",
                 "tick_offcpu_ms.serve", "upload_mb.serve")


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
    spans.records.append(("submit", lo + 1, lo + 2))
    return {"spans": spans}


def _served(t, reqs, tick_thread=7, stack_s=0.05, stack_cpu=0.02):
    """One tick at ``t`` admitting ``reqs`` (each submitted 0.1·(k+1) s
    before it), with its phases."""
    recs = []
    for k, i in enumerate(reqs):
        s1 = t - 0.1 * (k + 1)
        recs += [Rec("serve.submit", None, 1, s1 - 0.03, s1, 0.03,
                     {"req": i}),
                 Rec("serve.submit.featurize", "serve.submit", 1,
                     s1 - 0.03, s1 - 0.01, 0.02, {})]
    t1 = t + 0.4
    recs += [Rec("serve.tick", None, tick_thread, t, t1, 0.1,
                 {"reqs": list(reqs), "bucket": (128, 16)}),
             Rec("serve.tick.admit", "serve.tick", tick_thread, t, t + 0.01,
                 0.001, {}),
             Rec("serve.tick.stack", "serve.tick", tick_thread, t + 0.01,
                 t + 0.01 + stack_s, stack_cpu, {}),
             Rec("serve.tick.call", "serve.tick", tick_thread, t + 0.1,
                 t + 0.2, 0.05, {"bytes_in": 8_000_000}),
             Rec("serve.tick.unpack", "serve.tick", tick_thread, t + 0.3,
                 t + 0.32, 0.01, {})]
    return recs


def test_window_keeps_records_that_start_inside_it():
    recs = [Rec("a", None, 1, t, t + 5.0, 0.0, {})
            for t in (9.9, 10.0, 15.0, 19.99, 20.0)]
    kept = ps.window_records(_ctx(), recs)
    assert [r.t0 for r in kept] == [10.0, 15.0, 19.99]
    assert ps.window_records({}, recs) == []
    assert ps.window_records({"spans": harness.Spans()}, recs) == []


def test_queue_wait_joins_submit_to_its_tick():
    recs = _served(12.0, [0, 1]) + _served(14.0, [2, 3, 4])
    # waits 0.1, 0.2 and 0.1, 0.2, 0.3 s
    assert ps.queue_wait_p95_ms(recs) == pytest.approx(
        1e3 * np.percentile([0.1, 0.2, 0.1, 0.2, 0.3], 95))
    # a request without its submit record (started before the window)
    # is left out
    unjoined = [r for r in recs if r.attrs.get("req") != 4]
    assert ps.queue_wait_p95_ms(unjoined) == pytest.approx(
        1e3 * np.percentile([0.1, 0.2, 0.1, 0.2], 95))


def test_phase_means_offcpu_and_upload():
    recs = (_served(12.0, [0, 1], stack_s=0.05, stack_cpu=0.02)
            + _served(14.0, [2, 3, 4], stack_s=0.07, stack_cpu=0.07))
    assert ps.mean_ms(recs, "serve.tick.stack") == pytest.approx(60.0)
    assert ps.mean_ms(recs, "serve.tick.unpack") == pytest.approx(20.0)
    assert ps.mean_ms(recs, "serve.submit.featurize") == pytest.approx(20.0)
    # off-CPU per tick: admit 0.009 + stack (0.03 | 0) + unpack 0.01
    assert ps.tick_offcpu_ms(recs) == pytest.approx(
        1e3 * (0.009 + 0.01 + (0.03 + 0.0) / 2))
    # a phase on another thread is not the tick's, and a tick that found
    # the queue empty is no tick of the mean
    other = recs + [Rec("serve.tick.stack", "serve.tick", 99, 12.05, 12.3,
                        0.0, {}),
                    Rec("serve.tick", None, 7, 15.0, 15.001, 0.0,
                        {"reqs": []}),
                    Rec("serve.tick.admit", "serve.tick", 7, 15.0, 15.001,
                        0.0, {})]
    assert ps.tick_offcpu_ms(other) == pytest.approx(ps.tick_offcpu_ms(recs))
    assert ps.upload_mb(recs) == pytest.approx(2 * 8.0 / 5)


def test_no_records_no_number():
    assert ps.mean_ms([], "serve.tick.stack") is None
    assert ps.queue_wait_p95_ms([]) is None
    assert ps.tick_offcpu_ms([]) is None
    assert ps.upload_mb([]) is None


@pytest.mark.parametrize("metric", SERVE_READERS)
def test_readers_read_the_window(metric, monkeypatch):
    reader = harness.load_module("metrics", metric)
    recs = _served(12.0, [0, 1]) + _served(30.0, [2])     # 2nd: outside
    monkeypatch.setattr(ps, "program_records", lambda: recs)
    value = reader.read(_ctx())
    assert value is not None and value > 0
    monkeypatch.setattr(ps, "program_records", lambda: [])
    assert reader.read(_ctx()) is None
