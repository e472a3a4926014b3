"""The serving subsystem (``repro.serve``): padded-bucket exactness
(dense AND pallas mix), exact-fit bit-parity with the single-cohort
reference solve, continuous-batching queue semantics, per-bucket trace
economy, and the bounded-LRU cache hygiene layer
(``repro.clear_caches()`` / ``cache_stats()``).

A trained model is shared module-wide (one short meta-training run);
every test then serves NEW federations through it — the amortization
claim under test.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro import cache_stats, clear_caches
from repro import engine as E
from repro.configs.surf_paper import SMOKE, SPARSE_SMOKE
from repro.core import surf
from repro.core import unroll as U
from repro.core.tasks import resolve_task, sparse_recovery_task
from repro.data import synthetic
from repro.serve import (AsyncDriver, Bucket, BucketSpec,
                         FederationServer, ServeMetrics, pad_cohort,
                         pad_probe, serve_cache_key, slot_mask)
from repro.utils import spans
from repro.utils.cache import BoundedLRU

CFG = SMOKE
STEPS = 8
BUCKETS = BucketSpec(agent_sizes=(8, 16), row_sizes=(4, 8))


@pytest.fixture(scope="module")
def trained():
    mds = synthetic.make_meta_dataset(CFG, 3, seed=0)
    state, _, S = surf.train_surf(CFG, mds, steps=STEPS, seed=0,
                                  log_every=0)
    return state, S


def _cohort(n, t, seed):
    """A fresh federation: topology + dataset at (n agents, t test rows)."""
    cfg_r = dataclasses.replace(CFG, n_agents=n, test_per_agent=t)
    _, S = surf.make_problem(cfg_r, seed=seed)
    ds = synthetic.sample_dataset(cfg_r, seed=1000 + seed)
    return cfg_r, np.asarray(S), ds


def _server(theta, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    return FederationServer(CFG, theta, **kw)


# ---------------------------------------------------------- bucketing
def test_bucket_for_picks_smallest_fit():
    assert BUCKETS.bucket_for(6, 4) == Bucket(8, 4)
    assert BUCKETS.bucket_for(8, 5) == Bucket(8, 8)
    assert BUCKETS.bucket_for(9, 8) == Bucket(16, 8)


def test_bucket_for_overflow_raises():
    with pytest.raises(ValueError, match="exceeds the bucket grid"):
        BUCKETS.bucket_for(17, 4)


def test_pad_cohort_geometry():
    cfg_r, S, ds = _cohort(6, 4, seed=0)
    n, d = 6, resolve_task(CFG).dim
    W0 = np.ones((n, d), np.float32)
    Xl = np.ones((CFG.n_layers, n, CFG.batch_per_agent, CFG.feature_dim),
                 np.float32)
    Yl = np.ones((CFG.n_layers, n, CFG.batch_per_agent), np.int32)
    Sp, W0p, Xlp, Ylp, Xtep, Ytep = (np.asarray(a) for a in pad_cohort(
        S, W0, Xl, Yl, ds["Xte"], ds["Yte"], Bucket(8, 8)))
    mask, t_real = slot_mask(6, 4, Bucket(8, 8))
    assert Sp.shape == (8, 8) and not Sp[6:].any() and not Sp[:, 6:].any()
    assert not W0p[6:].any() and not Xlp[:, 6:].any()
    # padded test rows are row-0 copies for real agents, zero for padded
    np.testing.assert_array_equal(Xtep[:6, 4:],
                                  np.repeat(ds["Xte"][:, :1], 4, axis=1))
    assert not Xtep[6:].any() and not Ytep[6:].any()
    assert mask.tolist() == [True] * 6 + [False] * 2
    assert float(t_real) == 4.0


# --------------------------------------------------- padded exactness
@pytest.mark.parametrize("mix", [None, "pallas"])
def test_padded_bucket_matches_unpadded_solve(trained, mix):
    """A ragged cohort padded into a larger bucket solves bit-close to
    the unpadded single-cohort reference — weights AND eval metrics."""
    state, _ = trained
    cfg_r, S, ds = _cohort(6, 4, seed=3)
    srv = _server(state.theta, mix=mix)
    fut = srv.submit(S, ds, seed=7)
    srv.drain()
    res = fut.result()
    ref = surf.solve_federation(cfg_r, state, S, ds, seed=7)
    tol = 5e-5 if mix == "pallas" else 1e-5
    np.testing.assert_allclose(res["loss_per_layer"],
                               ref["loss_per_layer"], atol=tol, rtol=tol)
    np.testing.assert_allclose(res["acc_per_layer"], ref["acc_per_layer"],
                               atol=tol, rtol=tol)
    assert res["W"].shape == (6, resolve_task(CFG).dim)


def test_row_padded_bucket_matches_unpadded_solve(trained):
    """Row padding alone (t 4 -> bucket 8): the padded_local_* mean
    correction must recover the true test metrics."""
    state, _ = trained
    cfg_r, S, ds = _cohort(8, 4, seed=4)
    srv = _server(state.theta,
                  buckets=BucketSpec(agent_sizes=(8,), row_sizes=(8,)))
    fut = srv.submit(S, ds, seed=2)
    srv.drain()
    ref = surf.solve_federation(cfg_r, state, S, ds, seed=2)
    np.testing.assert_allclose(fut.result()["final_loss"],
                               ref["final_loss"], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fut.result()["final_acc"],
                               ref["final_acc"], atol=1e-5, rtol=1e-5)


def test_exact_fit_request_is_bit_close_to_evaluate_surf(trained):
    """No padding at all: the serve path reproduces the evaluate_surf
    RNG stream (fold_in(PRNGKey(1000+seed), 0)) — near-bit parity."""
    state, S = trained
    ds = synthetic.sample_dataset(CFG, seed=555)
    srv = _server(state.theta)
    fut = srv.submit(np.asarray(S), ds, seed=11)
    srv.drain()
    ref = surf.solve_federation(CFG, state, np.asarray(S), ds, seed=11)
    np.testing.assert_allclose(fut.result()["loss_per_layer"],
                               ref["loss_per_layer"], atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(fut.result()["acc_per_layer"],
                               ref["acc_per_layer"], atol=1e-6, rtol=1e-6)


def test_junk_in_pad_region_is_inert(trained):
    """Padding must be PROVABLY inert: poisoning the padded agents'
    rows of a padded batch changes nothing for real agents."""
    state, _ = trained
    cfg_r, S, ds = _cohort(6, 4, seed=5)
    srv = _server(state.theta)
    fut = srv.submit(S, ds, seed=1)
    req = srv._queue[0]
    Sp, W0p, Xlp, Ylp, Xtep, Ytep = req.arrays
    W0p = W0p.at[6:].set(1e6)   # junk where the mask says "padded agent"
    Xlp = Xlp.at[:, 6:].set(-3e5)
    Xtep = Xtep.at[6:].set(7e4)
    req.arrays = (Sp, W0p, Xlp, Ylp, Xtep, Ytep)
    srv.drain()
    ref = surf.solve_federation(cfg_r, state, S, ds, seed=1)
    np.testing.assert_allclose(fut.result()["final_loss"],
                               ref["final_loss"], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fut.result()["final_acc"],
                               ref["final_acc"], atol=1e-5, rtol=1e-5)


def test_sparse_task_serving_with_row_padding():
    """The ratio-of-sums NMSE metric needs its own padded correction —
    serve a sparse-recovery cohort padded in BOTH axes."""
    cfg = SPARSE_SMOKE
    task = sparse_recovery_task(cfg)
    mds = task.synth_datasets(cfg, 3, seed=0)
    state, _, _ = surf.train_surf(cfg, mds, steps=STEPS, seed=0,
                                  log_every=0)
    cfg_r = dataclasses.replace(cfg, n_agents=6, test_per_agent=4)
    _, S = surf.make_problem(cfg_r, seed=9)
    ds = task.synth_datasets(cfg_r, 1, seed=9)[0]
    srv = FederationServer(cfg, state.theta, buckets=BUCKETS, max_batch=2)
    fut = srv.submit(np.asarray(S), ds, seed=3)
    srv.drain()
    ref = surf.solve_federation(cfg_r, state, np.asarray(S), ds, seed=3)
    np.testing.assert_allclose(fut.result()["final_loss"],
                               ref["final_loss"], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(fut.result()["final_acc"],
                               ref["final_acc"], atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ queue semantics
def test_aging_prevents_bucket_starvation(trained):
    """A steady stream of one popular shape must not starve a rare
    shape: after max_wait_ticks passed-over ticks, the rare bucket's
    head wins admission outright."""
    state, _ = trained
    srv = _server(state.theta, max_batch=2, max_wait_ticks=2)
    _, S, ds = _cohort(12, 4, seed=90)          # the rare (16,4) request
    rare = srv.submit(S, ds, seed=0)
    futs = []
    for tick in range(3):
        for j in range(2):                      # two popular (8,4) per tick
            _, S, ds = _cohort(6, 4, seed=91 + 2 * tick + j)
            futs.append(srv.submit(S, ds, seed=tick))
        if tick < 2:
            # popular bucket is fuller (2 vs 1) — the rare one waits
            assert srv.tick() == 2 and not rare.done()
    # rare head has now been passed over max_wait_ticks=2 times: the
    # aging override serves its bucket alone despite lower occupancy
    assert srv.tick() == 1
    assert rare.done()
    assert sum(f.done() for f in futs) == 4     # 2 popular still queued
    srv.drain()
    assert all(f.done() for f in futs)


def test_fifo_head_defines_tick_bucket(trained):
    """Mixed-size stream: the head's bucket is served first; later
    same-bucket requests ride along, other buckets wait their turn."""
    state, _ = trained
    srv = _server(state.theta)
    futs = []
    for n, seed in [(6, 0), (12, 1), (8, 2), (16, 3)]:
        _, S, ds = _cohort(n, 4, seed=20 + seed)
        futs.append(srv.submit(S, ds, seed=seed))
    assert srv.tick() == 2            # head bucket (8,4): the n=6 and n=8
    assert futs[0].done() and futs[2].done()
    assert not futs[1].done() and not futs[3].done()
    assert srv.tick() == 2            # then bucket (16,4)
    assert all(f.done() for f in futs)
    assert srv.tick() == 0            # empty queue


def test_trace_count_one_per_warm_bucket_zero_at_request_rate(trained):
    """``warm()`` traces the solver once a (bucket, rung); ticks of any
    admitted count then trace nothing."""
    state, _ = trained
    srv = _server(state.theta)
    assert srv.rungs == (1, 2, 4)
    base = E.TRACE_COUNTS["serve"]
    warmed = srv.warm([(6, 4), (8, 4), (12, 4)])   # -> buckets (8,4),(16,4)
    assert len(warmed) == 2
    assert E.TRACE_COUNTS["serve"] - base == 2 * 3
    for i, n in enumerate([6, 8, 12, 16, 10, 6, 8, 6, 8]):
        _, S, ds = _cohort(n, 4, seed=40 + i)
        srv.submit(S, ds, seed=i)
    srv.drain()                # (8,4): 6 requests, rungs 4 and 2; (16,4): 3
    assert srv.metrics.ticks_by_slots == {2: 1, 4: 2}
    assert E.TRACE_COUNTS["serve"] - base == 2 * 3  # zero replay traces


# --------------------------------------------- device-resident slots
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@pytest.fixture(scope="module")
def compiles():
    """A running count of JAX trace and compile events in this process."""
    seen = [0]

    def on(event, duration, **_):
        if event in COMPILE_EVENTS:
            seen[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


@pytest.mark.parametrize("depth", ["fixed", "adaptive"])
def test_queued_request_holds_device_slot_of_bucket_shape(trained, depth):
    """``submit`` queues the padded slot as device arrays of the bucket's
    shapes, bitwise ``pad_cohort`` of the eager ``featurize_cohort``."""
    state, _ = trained
    srv = _server(state.theta, depth=depth)
    cfg_r, S, ds = _cohort(6, 4, seed=95)
    srv.submit(S, ds, seed=3, q=1)
    req = srv._queue[0]
    assert all(isinstance(a, jax.Array) for a in req.arrays)
    d, L = resolve_task(CFG).dim, CFG.n_layers
    b, F, p = CFG.batch_per_agent, CFG.feature_dim, CFG.probe_size
    shapes = [(8, 8), (8, d), (L, 8, b, F), (L, 8, b), (8, 4, F), (8, 4)]
    if depth == "adaptive":
        shapes += [(8, p, F), (8, p)]
    assert [a.shape for a in req.arrays] == shapes
    assert isinstance(req.mask, np.ndarray)
    assert req.mask.tolist() == [True] * 6 + [False] * 2
    key = jax.random.fold_in(jax.random.PRNGKey(1003), 1)
    batch = {k: jax.numpy.asarray(v) for k, v in ds.items()}
    ref = pad_cohort(S, *U.featurize_cohort(key, batch, cfg_r),
                     batch["Xte"], batch["Yte"], Bucket(8, 4))
    if depth == "adaptive":
        ref += pad_probe(*U.probe_batch(batch, cfg_r), Bucket(8, 4))
    for got, want in zip(req.arrays, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


KS = (1, 3, 8)


@pytest.fixture(scope="module")
def ticked(trained, tmp_path_factory):
    """One server (``max_batch=8``) answering one tick of each ``k`` in
    ``KS`` under a profiler session: {k: (requests, futures, the output
    of the executable of the rung the tick runs (1, 4, 8) on the
    host-stacked numpy batch of the same slots, zero-filled past the
    ``k`` admitted, the tick's ``serve.tick.call`` and
    ``serve.tick.unpack`` records)}."""
    state, _ = trained
    srv = _server(state.theta, max_batch=8)
    ticks = {}
    with jax.profiler.trace(str(tmp_path_factory.mktemp("ticked"))):
        for k, B in zip(KS, (1, 4, 8)):
            futs = [srv.submit(*_cohort([6, 8][i % 2], 4, seed=100 + i)[1:],
                               seed=i) for i in range(k)]
            reqs = list(srv._queue)
            slots = [[np.asarray(a) for a in r.arrays] for r in reqs]
            slots += [[np.zeros_like(a) for a in slots[0]]] * (B - k)
            args = [np.stack(a) for a in zip(*slots)]
            mask = np.stack([r.mask for r in reqs]
                            + [np.zeros(8, bool)] * (B - k))
            t_real = np.array([r.t_real for r in reqs] + [4.0] * (B - k),
                              np.float32)
            solve = srv._solver(Bucket(8, 4), B)
            ref = jax.device_get(solve(args[0], state.theta, *args[1:],
                                       mask, t_real))
            t0 = time.perf_counter()
            assert srv.tick() == k
            call, unpack = ([r for r in spans.records()
                             if r.t0 >= t0 and r.name == name]
                            for name in ("serve.tick.call",
                                         "serve.tick.unpack"))
            ticks[k] = reqs, futs, ref, call[0], unpack[0]
    return ticks


@pytest.mark.parametrize("k", KS)
def test_tick_matches_host_stacked_batch_bitwise(ticked, k):
    """A tick's device-assembled batch solves to exactly what the
    executable of the rung it runs returns on the host-stacked numpy
    batch of the same slots, zero-filled past the ``k`` admitted
    requests: the empty slots' repeats of the first slot reach no
    result, and the per-slot fetch brings each admitted slot's W
    unchanged."""
    reqs, futs, ref, call, _ = ticked[k]
    assert call.attrs["slots"] == len(ref["final_loss"])
    d = resolve_task(CFG).dim
    for i, (r, f) in enumerate(zip(reqs, futs)):
        res = f.result()
        np.testing.assert_array_equal(res["W"], ref["W"][i, :r.n_real, :d])
        for key in ("final_loss", "final_acc", "loss_per_layer",
                    "acc_per_layer"):
            np.testing.assert_array_equal(res[key], ref[key][i])


@pytest.mark.parametrize("k", KS)
def test_unpack_counts_the_bytes_it_brings(ticked, k):
    """``bytes_out`` of ``serve.tick.unpack``: the ``k`` admitted slots'
    W (n_pad × dim float32 each; the empty slots' W is not fetched) and
    the small leaves of all the rung's slots."""
    _, _, ref, _, unpack = ticked[k]
    small = sum(v.nbytes for key, v in ref.items() if key != "W")
    assert unpack.attrs["bytes_out"] == (
        k * 8 * resolve_task(CFG).dim * 4 + small)


def test_results_do_not_share_memory(trained):
    state, _ = trained
    srv = _server(state.theta)
    futs = [srv.submit(*_cohort(6, 4, seed=110 + i)[1:], seed=i)
            for i in range(2)]
    srv.tick()
    a, b = (f.result() for f in futs)
    for key in a:
        assert a[key].flags.writeable
        assert not np.shares_memory(a[key], b[key]), key


def test_warm_then_one_request_leaves_ticks_compile_free(trained,
                                                         compiles):
    """After ``warm()`` and one warm request, further requests of the
    same shape trace and compile nothing, at submit or at the tick."""
    state, _ = trained
    cohorts = [_cohort(6, 4, seed=120 + i)[1:] for i in range(4)]
    srv = _server(state.theta)
    srv.warm([(6, 4)])
    srv.submit(*cohorts[0], seed=0)
    assert srv.tick() == 1
    seen, traces = compiles[0], E.TRACE_COUNTS["serve"]
    for i, (S, ds) in enumerate(cohorts[1:]):
        srv.submit(S, ds, seed=1 + i)
    assert srv.tick() == 3
    assert compiles[0] == seen
    assert E.TRACE_COUNTS["serve"] == traces


@pytest.fixture(scope="module")
def warmed(trained):
    """A server (``max_batch=4``) after ``warm()`` alone."""
    state, _ = trained
    srv = _server(state.theta)
    srv.warm([(6, 4)])
    return srv


@pytest.mark.parametrize("k", [1, 2, 3, 4])
def test_warm_leaves_ticks_at_every_admitted_count_compile_free(
        warmed, compiles, k):
    """After ``warm()``, a tick of any admitted count up to ``max_batch``
    traces and compiles nothing: the per-slot fetch takes its slot as a
    traced index, one program a bucket shape."""
    cohorts = [_cohort(6, 4, seed=130 + 10 * k + i)[1:] for i in range(k)]
    seen, traces = compiles[0], E.TRACE_COUNTS["serve"]
    futs = [warmed.submit(S, ds, seed=i) for i, (S, ds) in enumerate(cohorts)]
    assert warmed.tick() == k
    assert all(f.done() for f in futs)
    assert compiles[0] == seen
    assert E.TRACE_COUNTS["serve"] == traces


def test_metrics_summary_fields(trained):
    state, _ = trained
    srv = _server(state.theta)
    for i in range(3):
        _, S, ds = _cohort(6, 4, seed=60 + i)
        srv.submit(S, ds, seed=i)
    srv.drain()
    s = srv.metrics.summary()
    assert s["requests_completed"] == 3
    assert s["federations_per_sec"] > 0
    assert s["latency_p99_ms"] >= s["latency_p50_ms"] > 0
    assert s["occupancy"] == pytest.approx(3 / 4)  # 3 requests, B=4
    # useful 3*6*4 cells of 4*8*4 padded slots
    assert s["pad_waste"] == pytest.approx(1 - 72 / 128)
    assert s["per_bucket_ticks"] == {"n8xt4": 1}
    assert s["ticks_by_slots"] == {4: 1}


def test_metrics_count_the_slots_of_the_rung_run(trained):
    """One request on a ``max_batch=4`` server runs the one-slot rung:
    every slot run is occupied, and only that slot's cells are padded."""
    state, _ = trained
    srv = _server(state.theta)
    _, S, ds = _cohort(6, 4, seed=63)
    srv.submit(S, ds, seed=0)
    srv.drain()
    s = srv.metrics.summary()
    assert s["occupancy"] == 1.0
    assert s["ticks_by_slots"] == {1: 1}
    assert s["pad_waste"] == pytest.approx(1 - 24 / 32)


# ----------------------------------------------------------- validation
def test_star_config_rejected(trained):
    state, _ = trained
    star = dataclasses.replace(CFG, topology="star", filter_taps=1)
    with pytest.raises(ValueError, match="star-topology serving"):
        FederationServer(star, state.theta)


def test_baked_s_mix_rejected(trained):
    state, _ = trained
    with pytest.raises(ValueError, match="per-request topologies"):
        _server(state.theta, mix="ring")


def test_shape_mismatch_rejected(trained):
    state, _ = trained
    srv = _server(state.theta)
    _, S, ds = _cohort(6, 4, seed=70)
    with pytest.raises(ValueError, match="agents but S is"):
        srv.submit(S[:5, :5], ds)
    with pytest.raises(ValueError, match="must be square"):
        srv.submit(S[:5], ds)
    with pytest.raises(ValueError, match="missing keys"):
        srv.submit(S, {"Xtr": ds["Xtr"]})


# -------------------------------------------------------- cache hygiene
def test_serve_cache_key_shape_and_task_separation():
    k1 = serve_cache_key(CFG, Bucket(8, 4), 4, "relu")
    k2 = serve_cache_key(CFG, Bucket(16, 4), 4, "relu")
    k3 = serve_cache_key(CFG, Bucket(8, 4), 8, "relu")
    assert len({k1, k2, k3}) == 3
    # cohort-size cfg fields are scrubbed: requests of any true size
    # share the bucket executable
    assert serve_cache_key(dataclasses.replace(CFG, n_agents=6),
                           Bucket(8, 4), 4, "relu") == k1
    sk = serve_cache_key(SPARSE_SMOKE, Bucket(8, 4), 4, "relu")
    assert sk != k1


def test_bucket_cache_lru_eviction_and_stats(trained):
    """``max_buckets`` counts buckets: the cache holds that many buckets'
    executables at each of the server's 3 rungs."""
    state, _ = trained
    srv = _server(state.theta, max_buckets=1)
    srv.warm([(6, 4)])
    srv.warm([(12, 4)])                 # evicts the (8,4) executables
    st = srv.cache_stats()
    assert st["size"] == 3 and st["evictions"] == 3
    base = E.TRACE_COUNTS["serve"]
    srv.warm([(6, 4)])                  # rebuild after eviction: retrace
    assert E.TRACE_COUNTS["serve"] - base == 3


def test_clear_caches_selective_and_stats(trained):
    state, _ = trained
    srv = _server(state.theta)
    srv.warm([(6, 4)])
    name = srv._cache.name
    assert name.startswith("serve-buckets")
    stats = cache_stats()
    assert stats[name]["size"] == len(srv.rungs)
    assert "engine" in stats and "surf-eval" in stats
    engine_size = stats["engine"]["size"]
    # selective clear: ONLY the named serve cache empties
    assert clear_caches(name) == [name]
    assert cache_stats()[name]["size"] == 0
    assert cache_stats()["engine"]["size"] == engine_size
    with pytest.raises(KeyError, match="unknown cache name"):
        clear_caches("no-such-cache")


def test_per_server_caches_die_with_their_server(trained):
    state, _ = trained
    srv = _server(state.theta)
    name = srv._cache.name
    assert name in cache_stats()
    del srv
    assert name not in cache_stats()    # weak registry pruned


def test_bounded_lru_mapping_protocol():
    c = BoundedLRU(maxsize=2)
    c["a"], c["b"] = 1, 2
    assert "a" in c and c["a"] == 1     # refreshes recency
    c["c"] = 3                          # evicts LRU "b"
    assert "b" not in c and set(c) == {"a", "c"}
    assert c.get_or_build("a", lambda: 99) == 1
    assert c.get_or_build("d", lambda: 4) == 4
    s = c.stats()
    assert s["evictions"] >= 1 and s["hits"] >= 2 and s["misses"] == 1


# ------------------------------------------------------------- smoke
def test_serve_smoke_mini_trace(trained):
    """Fast tier-1 smoke: warm 2 buckets, replay a 12-request mixed
    trace, spot-check parity — the bench's contract at test scale."""
    state, _ = trained
    srv = _server(state.theta)
    srv.warm([(8, 4), (16, 4)])
    base = E.TRACE_COUNTS["serve"]
    reqs = []
    for i in range(12):
        n = [6, 8, 12, 16][i % 4]
        cfg_r, S, ds = _cohort(n, 4, seed=80 + i)
        reqs.append((cfg_r, S, ds, srv.submit(S, ds, seed=i)))
    srv.drain()
    assert E.TRACE_COUNTS["serve"] == base
    cfg_r, S, ds, fut = reqs[5]
    ref = surf.solve_federation(cfg_r, state, S, ds, seed=5)
    np.testing.assert_allclose(fut.result()["final_acc"],
                               ref["final_acc"], atol=1e-5, rtol=1e-5)
    assert srv.metrics.summary()["requests_completed"] == 12


# ------------------------------------------------- deadline admission
def test_deadline_beats_fuller_bucket(trained):
    """A request about to miss its deadline wins admission over a
    fuller bucket: deadline urgency outranks occupancy (and aging)."""
    state, _ = trained
    srv = _server(state.theta, max_batch=4)
    _, S, ds = _cohort(12, 4, seed=60)          # lone (16,4) request,
    urgent = srv.submit(S, ds, seed=0, deadline_ticks=1)   # due NOW
    bulk = []
    for j in range(3):                          # fuller (8,4) bucket
        _, S, ds = _cohort(6, 4, seed=61 + j)
        bulk.append(srv.submit(S, ds, seed=j))
    assert srv.tick() == 1                      # deadline bucket first
    assert urgent.done() and not any(f.done() for f in bulk)
    assert srv.tick() == 3
    assert all(f.done() for f in bulk)


def test_deadline_validation(trained):
    state, _ = trained
    srv = _server(state.theta)
    _, S, ds = _cohort(6, 4, seed=65)
    with pytest.raises(ValueError, match="deadline_ticks"):
        srv.submit(S, ds, seed=0, deadline_ticks=0)


def test_bucket_cache_in_metrics_summary(trained):
    """The server's bucket-executable LRU stats ride along in every
    metrics snapshot — cache churn diagnosable next to pad waste."""
    state, _ = trained
    srv = _server(state.theta)
    _, S, ds = _cohort(6, 4, seed=66)
    srv.submit(S, ds, seed=0)
    srv.drain()
    summ = srv.metrics.summary()
    assert summ["bucket_cache"] == srv.cache_stats()
    assert summ["bucket_cache"]["misses"] >= 1


def test_federations_per_sec_is_over_wall_time():
    """Throughput runs from the earliest submit of a completed request to
    the last completion, not over solver time alone."""
    m = ServeMetrics()
    m.record_tick(Bucket(8, 4), 2, 4, 1.0, 2.0, [0.5, 0.3], 0.1,
                  done_at=10.0)
    m.record_tick(Bucket(8, 4), 1, 4, 1.0, 2.0, [0.2], 0.1, done_at=11.0)
    assert m.summary()["federations_per_sec"] == pytest.approx(3 / 1.5)
    assert ServeMetrics().summary()["federations_per_sec"] == 0.0


# ------------------------------------------------------- async driver
def _async_vs_manual(theta, queue_first):
    """Ten mixed-bucket requests through a manual ``drain()`` and through
    an ``AsyncDriver``: (manual results, async results, driver stats).
    With ``queue_first`` every request is queued before the tick loop
    starts; otherwise the submits land while it ticks."""
    reqs = [_cohort([6, 8, 12, 16][i % 4], 4, seed=70 + i)
            for i in range(10)]

    manual = _server(theta)
    m_futs = [manual.submit(S, ds, seed=i)
              for i, (_, S, ds) in enumerate(reqs)]
    manual.drain()

    driver = AsyncDriver(_server(theta))
    if queue_first:
        a_futs = [driver.submit(S, ds, seed=i)
                  for i, (_, S, ds) in enumerate(reqs)]
        with driver:
            driver.wait(a_futs, timeout_s=120.0)
    else:
        with driver:
            a_futs = [driver.submit(S, ds, seed=i)
                      for i, (_, S, ds) in enumerate(reqs)]
            driver.wait(a_futs, timeout_s=120.0)
    return ([f.result() for f in m_futs], [f.result() for f in a_futs],
            driver.stats())


def test_async_driver_matches_manual_tick_loop(trained):
    """The background tick loop adds no scheduling of its own: submits
    landing while it ticks give the same per-request results as a manual
    tick loop over the same submission order. Padding is inert, but a
    tick that admits fewer requests runs a smaller rung, whose
    executable may round differently, so the results agree within the
    1e-5 that ``tests/test_serve_theta_split.py`` holds rungs to."""
    state, _ = trained
    manual, served, stats = _async_vs_manual(state.theta, queue_first=False)
    for m, a in zip(manual, served):
        for key in ("final_loss", "final_acc"):
            np.testing.assert_allclose(np.asarray(a[key]),
                                       np.asarray(m[key]), rtol=0,
                                       atol=1e-5)
    assert stats["requests_completed"] == len(manual)
    assert stats["ticks"] >= 1 and not stats["running"]


def test_async_driver_on_a_queued_stream_matches_manual_bitwise(trained):
    """On a queue filled before the loop starts the driver admits the
    same ticks as ``drain()``, so each request runs at the same rung and
    its results are bit-identical."""
    state, _ = trained
    manual, served, stats = _async_vs_manual(state.theta, queue_first=True)
    for m, a in zip(manual, served):
        for key in ("final_loss", "final_acc"):
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(m[key]))
    assert stats["requests_completed"] == len(manual)
    assert stats["ticks"] >= 1 and not stats["running"]


def test_async_driver_stop_without_drain_leaves_queue(trained):
    """``stop(drain=False)`` exits after the in-flight tick; queued
    requests stay pending on the untouched server and a later manual
    drain completes them."""
    state, _ = trained
    srv = _server(state.theta)
    driver = AsyncDriver(srv)                   # never started: queue
    _, S, ds = _cohort(6, 4, seed=85)           # only drains manually
    fut = driver.submit(S, ds, seed=0)
    driver.stop(drain=False)
    assert not fut.done() and srv.pending() == 1
    srv.drain()
    assert fut.done() and srv.pending() == 0
