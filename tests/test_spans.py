"""Program spans (``repro.utils.spans``) and the ``surf/`` named scopes.

Spans record only under a profiler session: off, a span runs its body and
leaves nothing; on, each lands both in the in-memory buffer and, as a
``surf.*`` host event, in the profile, on a shared clock. The served
tick's spans nest, and request ids join ``submit`` to ``tick``. The eight
scopes reach the lowered meta-step, and the three that apply reach the
lowered serve solver.
"""
import dataclasses
import glob
import os
import sys
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.data import synthetic
from repro.engine import core as EC
from repro.serve import Bucket, BucketSpec, FederationServer, make_bucket_solver
from repro.utils import spans

SCOPES = ("surf/featurize", "surf/mix", "surf/perceptron", "surf/loss",
          "surf/constraints", "surf/clip", "surf/adam", "surf/dual")
TICK_PHASES = ("serve.tick.admit", "serve.tick.stack", "serve.tick.call",
               "serve.tick.wait", "serve.tick.unpack")


def _busy(seconds):
    """Spin until this thread has used ``seconds`` of CPU time."""
    end = time.thread_time() + seconds
    x = 0
    while time.thread_time() < end:
        x += 1
    return x


def _first_span():
    """The first event a thread writes into a profile pays the tracer's
    set-up for that thread; spans whose durations are compared with the
    profile come after it."""
    with spans.span("first"):
        pass


def _since(t0):
    return [r for r in spans.records() if r.t0 >= t0]


def _host_events(trace_dir):
    """{name: [duration s, ...]} of the ``surf.*`` host events of the one
    profile under ``trace_dir``, in order of start."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        events = sorted((e.start_ns, e.name, e.duration_ns)
                        for ln in plane.lines for e in ln.events
                        if e.name.startswith(spans.PREFIX))
        for _, name, dur in events:
            out.setdefault(name[len(spans.PREFIX):], []).append(dur * 1e-9)
    return out


def test_span_off_records_nothing_and_runs_body_once():
    assert not TraceAnnotation.is_enabled()
    before = spans.records()
    runs = 0
    with spans.span("off", a=1) as s:
        runs += 1
        s.set(b=2)
    assert runs == 1
    assert spans.records() == before


def test_span_on_records_parent_thread_cpu_and_attrs(tmp_path):
    t_start = time.perf_counter()
    other = []

    def in_thread():
        with spans.span("other"):
            other.append(threading.get_ident())

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("outer", a=1) as outer:
            _busy(0.02)
            with spans.span("inner"):
                time.sleep(0.01)
            th = threading.Thread(target=in_thread)
            th.start()
            th.join()
            outer.set(b=[3, 4])
    recs = {r.name: r for r in _since(t_start)}
    assert set(recs) == {"outer", "inner", "other"}
    o, i, x = recs["outer"], recs["inner"], recs["other"]
    assert o.parent is None and i.parent == "outer" and x.parent is None
    assert o.thread == i.thread == threading.get_ident()
    assert x.thread == other[0] != o.thread
    assert o.attrs == {"a": 1, "b": [3, 4]} and i.attrs == {}
    assert o.t0 <= i.t0 <= i.t1 <= o.t1
    # the spin is CPU time, the sleep is not
    assert 0.02 <= o.cpu_s <= o.t1 - o.t0 + 1e-3
    assert i.cpu_s < 0.5 * (i.t1 - i.t0)


def test_span_durations_match_the_profile(tmp_path):
    t_start = time.perf_counter()
    with jax.profiler.trace(str(tmp_path)):
        _first_span()
        for ms in (3, 11, 7):
            with spans.span("timed", ms=ms):
                time.sleep(ms / 1e3)
    mem = [r.t1 - r.t0 for r in _since(t_start) if r.name == "timed"]
    prof = _host_events(str(tmp_path))["timed"]
    assert len(mem) == len(prof) == 3
    np.testing.assert_allclose(mem, prof, atol=1e-3)


def test_spans_from_many_threads_keep_their_own_parents(tmp_path):
    """More threads than cores, switching often: every record names its
    own thread's enclosing span, and none is lost."""
    n_threads, n_spans = 4 * (os.cpu_count() or 1), 50
    t_start = time.perf_counter()

    def work(k):
        for i in range(n_spans):
            with spans.span(f"outer{k}", i=i):
                with spans.span(f"inner{k}", i=i):
                    pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    recs = _since(t_start)
    assert len(recs) == 2 * n_threads * n_spans
    outers = {(r.name[5:], r.attrs["i"]): r for r in recs
              if r.name.startswith("outer")}
    for r in recs:
        if r.name.startswith("outer"):
            assert r.parent is None
            continue
        o = outers[(r.name[5:], r.attrs["i"])]
        assert r.parent == o.name and r.thread == o.thread
        assert o.t0 <= r.t0 <= r.t1 <= o.t1


def test_records_buffer_is_bounded(tmp_path, monkeypatch):
    assert spans._RECORDS.maxlen == spans.MAX_RECORDS
    monkeypatch.setattr(spans, "_RECORDS", deque(maxlen=4))
    with jax.profiler.trace(str(tmp_path)):
        for i in range(10):
            with spans.span("bounded", i=i):
                pass
    assert [r.attrs["i"] for r in spans.records()] == [6, 7, 8, 9]


# ---------------------------------------------------------- serving
@pytest.fixture(scope="module")
def served_tick(tmp_path_factory):
    """Three requests submitted and answered by one tick, under a profile:
    (records, profile events, server, futures, submitted arrays)."""
    theta = EC.init_state(jax.random.PRNGKey(0), SMOKE).theta
    srv = FederationServer(SMOKE, theta, max_batch=4,
                           buckets=BucketSpec(agent_sizes=(8,),
                                              row_sizes=(4,)))
    srv.warm([(6, 4)])
    cohorts = []
    for i, n in enumerate((4, 6, 6)):
        cfg_r = dataclasses.replace(SMOKE, n_agents=n, test_per_agent=4)
        _, S = surf.make_problem(cfg_r, seed=i)
        cohorts.append((np.asarray(S),
                        synthetic.sample_dataset(cfg_r, seed=100 + i)))
    trace_dir = str(tmp_path_factory.mktemp("served"))
    t_start = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        _first_span()
        futs = [srv.submit(S, ds, seed=i)
                for i, (S, ds) in enumerate(cohorts)]
        queued = [r.arrays for r in srv._queue]
        assert srv.tick() == 3
    recs = [r for r in _since(t_start) if r.name != "first"]
    return recs, _host_events(trace_dir), srv, futs, queued


def test_served_tick_spans_nest_and_join(served_tick):
    recs, _, srv, futs, queued = served_tick
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    submits = by["serve.submit"]
    assert len(submits) == 3 and len(by["serve.tick"]) == 1
    tick = by["serve.tick"][0]
    assert tick.parent is None
    assert tick.attrs["reqs"] == [r.attrs["req"] for r in submits]
    assert tick.attrs["bucket"] == (8, 4)
    for sub in submits:
        kids = [r for r in recs if r.parent == "serve.submit"
                and sub.t0 <= r.t0 and r.t1 <= sub.t1]
        assert [k.name for k in kids] == ["serve.submit.featurize",
                                          "serve.submit.pad"]
    phases = [r for r in recs if r.parent == "serve.tick"]
    assert [r.name for r in phases] == list(TICK_PHASES)
    assert all(tick.t0 <= r.t0 <= r.t1 <= tick.t1 for r in phases)
    assert all(r.thread == tick.thread for r in phases)
    # request data stays on the device from submit to the solve: the
    # call's host bytes are the 4 slots' masks (8 bools) and t_real
    call = by["serve.tick.call"][0]
    assert all(isinstance(a, jax.Array) for a in queued[0])
    assert call.attrs["bytes_in"] == 4 * (8 * 1 + 4)
    # latency runs from submit's entry, so featurization counts in it
    wait = by["serve.tick.wait"][0]
    for f, sub in zip(futs, submits):
        assert f.latency >= wait.t1 - sub.t0


def test_served_tick_durations_match_the_profile(served_tick):
    recs, prof, *_ = served_tick
    names = {r.name for r in recs}
    assert names == {"serve.submit", "serve.submit.featurize",
                     "serve.submit.pad", "serve.tick", *TICK_PHASES}
    for name in names:
        mem = [r.t1 - r.t0 for r in sorted(recs, key=lambda r: r.t0)
               if r.name == name]
        assert len(prof[name]) == len(mem), name
        np.testing.assert_allclose(mem, prof[name], atol=1e-3, err_msg=name)


# ---------------------------------------------------------- scopes
def _scopes_in(lowered):
    text = lowered.as_text(debug_info=True)
    return {s for s in SCOPES if s in text}


def test_named_scopes_reach_the_lowered_meta_step():
    cfg = SMOKE
    key = jax.random.PRNGKey(0)
    state = EC.init_state(key, cfg)
    ds = synthetic.make_meta_dataset(cfg, 1, seed=0)[0]
    batch = {k: jnp.asarray(ds[k]) for k in ("Xtr", "Ytr", "Xte", "Yte")}
    _, S = surf.make_problem(cfg, seed=0)
    step, _ = EC.make_meta_step(cfg, S)
    assert _scopes_in(step.lower(state, batch, key)) == set(SCOPES)
    featurize = jax.jit(lambda k, b: U.featurize_cohort(k, b, cfg))
    assert _scopes_in(featurize.lower(key, batch)) == {"surf/featurize"}


def test_named_scopes_reach_the_lowered_serve_solver():
    cfg, B, bucket = SMOKE, 2, Bucket(8, 4)
    theta = EC.init_state(jax.random.PRNGKey(0), cfg).theta
    task = resolve_task(cfg)
    n, t, L = bucket.n_agents, bucket.rows, cfg.n_layers
    b, F, d = cfg.batch_per_agent, task.feat_dim, task.dim
    f32, y = np.float32, task.label_dtype
    args = (np.zeros((B, n, n), f32), theta, np.zeros((B, n, d), f32),
            np.zeros((B, L, n, b, F), f32), np.zeros((B, L, n, b), y),
            np.zeros((B, n, t, F), f32), np.zeros((B, n, t), y),
            np.zeros((B, n), bool), np.full((B,), t, f32))
    solve = make_bucket_solver(cfg, bucket, B)
    assert _scopes_in(solve.lower(*args)) == {"surf/mix", "surf/perceptron",
                                              "surf/loss"}
