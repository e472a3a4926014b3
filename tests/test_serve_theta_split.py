"""Serving with θ's perceptron split by columns over a ('agent', 'theta')
mesh, on 4 virtual CPU devices in a subprocess (so that the single-device
suite runs it), plus the layout chooser and the benchmark's layer-blocked
reference, which need no mesh.

The subprocess (``_probe``) serves one tiny 62-class federation set
(F = 8, n = 12, L = 2: d = 558, which 4 does not divide) through
``FederationServer`` on one device, on meshes that split θ 2 and 4 ways
and on one that splits request slots 4 ways, in ticks of 1, 3 and 8
admitted requests, then of every count from 1 to 8 to read the rung each
tick runs, and reports what the tests below compare."""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import SURFConfig
from repro.launch.mesh import serve_layout, serve_theta_bytes

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CFG = SURFConfig(n_agents=12, n_layers=2, filter_taps=2, feature_dim=8,
                 n_classes=62, batch_per_agent=4, train_per_agent=8,
                 test_per_agent=4, eps=0.05, topology="regular", degree=3)
SPLITS = (2, 4)
# the server's mesh ('agent', 'theta') by layout; None: one device
LAYOUTS = {"one": None, "split_2": (2, 2), "split_4": (1, 4),
           "requests_4": (4, 1)}
KS = (1, 3, 8)
WARM_KS = (1, 2, 3, 4)
# the rung a tick of k = 1..max_batch admitted requests runs, by layout
RUNGS = {"one": (1, 2, 4, 4, 8, 8, 8, 8),
         "split_2": (2, 2, 4, 4, 8, 8, 8, 8),
         "split_4": (1, 2, 4, 4, 8, 8, 8, 8),
         "requests_4": (4, 4, 4, 4, 8, 8, 8, 8),
         "max_batch_3": (1, 2, 3)}
V5E_LIMIT = 15.75e9
SEED = 2 ** 40 + 12345


def _theta(cfg):
    """Seeded random θ whose perceptron and bias act on the answer."""
    import jax
    from repro.core import unroll as U
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    theta = U.init_udgd(k[0], cfg)
    return {"h": theta["h"], "M": 10.0 * theta["M"],
            "d": 0.1 * jax.random.normal(k[1], theta["d"].shape)}


def _cohorts(cfg):
    from repro.core import surf
    from repro.data import synthetic
    out = []
    for i, n in enumerate([12, 10] * 4):
        cfg_r = dataclasses.replace(cfg, n_agents=n)
        _, S = surf.make_problem(cfg_r, seed=50 + i)
        out.append((cfg_r, np.asarray(S),
                    synthetic.sample_dataset(cfg_r, seed=1050 + i)))
    return out


def _probe():
    """Runs under 4 virtual CPU devices; prints one JSON object."""
    import jax
    from jax.sharding import AxisType
    from repro.core import surf
    from repro.engine.core import TRACE_COUNTS
    from repro.serve import BucketSpec, FederationServer
    from repro.sharding.surf_rules import place_theta
    assert len(jax.devices()) == 4
    sys.path.insert(0, str(BENCH))
    import harness
    import surfgen

    def mesh(shape):
        return jax.make_mesh(shape, ("agent", "theta"),
                             axis_types=(AxisType.Auto,) * 2)

    theta = _theta(CFG)
    state = types.SimpleNamespace(theta=theta)
    reqs = _cohorts(CFG)
    d = int(theta["M"].shape[-1])
    buckets = BucketSpec(agent_sizes=(16,), row_sizes=(4,))
    out = {"d": d}
    with jax.default_matmul_precision("highest"):
        refs = [surf.solve_federation(c, state, S, ds, seed=i)
                for i, (c, S, ds) in enumerate(reqs[:3])]
        served = {}
        for layout, shape in LAYOUTS.items():
            srv = FederationServer(
                CFG, theta, max_batch=8, buckets=buckets,
                mesh=None if shape is None else mesh(shape))
            served[layout] = {}
            for k in KS:
                futs = [srv.submit(S, ds, seed=i)
                        for i, (_, S, ds) in enumerate(reqs[:k])]
                whole = _whole_fetch(srv, d)
                assert srv.tick() == k
                served[layout][k] = [f.result() for f in futs]
                out[f"exact_{layout}_{k}"] = all(
                    np.array_equal(a[key], b[key])
                    for a, b in zip(served[layout][k], whole) for key in b)
            out[f"w_shapes_{layout}"] = [
                list(r["W"].shape) for k in KS for r in served[layout][k]]
            # request 0 alone (the smallest rung) against it in a full batch
            out[f"rung_gap_{layout}"] = max(
                float(np.max(np.abs(served[layout][1][0][key]
                                    - served[layout][8][0][key])))
                for key in ("W", "final_loss", "loss_per_layer"))
            out[f"rungs_{layout}"] = _rungs_run(srv, reqs, srv.max_batch)
            if shape is None:
                continue
            out[f"one_gap_{layout}"] = max(
                float(np.max(np.abs(a[key] - b[key])))
                for k in KS
                for a, b in zip(served[layout][k], served["one"][k])
                for key in ("W", "final_loss", "loss_per_layer"))
            if shape[1] == 1:
                continue
            split = shape[1]
            out[f"ref_gap_{split}"] = max(
                max(abs(float(r[k]) - float(ref[k]))
                    for k in ("final_loss", "final_acc"))
                for r, ref in zip(served[layout][3], refs))
            out[f"one_gap_{split}"] = out[f"one_gap_{layout}"]
            out[f"w_shape_{split}"] = list(served[layout][3][0]["W"].shape)
            # the solver's own output, padded columns included
            bucket = buckets.bucket_for(12, 4)
            slot = srv._slot(reqs[0][1], reqs[0][2], bucket, 0, 0)
            B = srv.rungs[0]
            stacked, m, t = srv._batch(bucket, B, [slot],
                                       [np.ones(16, bool)], [np.float32(4)])
            raw = srv._solver(bucket, B)(stacked[0], srv.theta,
                                         *stacked[1:], m, t)
            W = np.asarray(raw["W"])
            out[f"cols_{split}"] = W.shape[-1]
            out[f"pad_max_{split}"] = float(np.abs(W[..., d:]).max(
                initial=0.0))
            out[f"theta_pad_max_{split}"] = float(np.abs(np.asarray(
                srv.theta["M"])[..., d:]).max(initial=0.0))
            out[f"theta_block_{split}"] = list(
                srv.theta["M"].addressable_shards[0].data.shape)

        srv = FederationServer(CFG, theta, max_batch=3, buckets=buckets)
        out["rungs_max_batch_3"] = _rungs_run(srv, reqs, 3)

    # warm() traces once a rung; after it, a tick on the 2-D mesh of any
    # admitted count traces and compiles nothing
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, dur, **_: compiles.append(e)
        if e in harness.COMPILE_EVENTS else None)
    srv = FederationServer(CFG, theta, max_batch=4, buckets=buckets,
                           mesh=mesh((2, 2)))
    traces = TRACE_COUNTS["serve"]
    srv.warm([(12, 4), (10, 4)])
    out["warm_rungs"] = list(srv.rungs)
    out["warm_traces"] = TRACE_COUNTS["serve"] - traces
    traces, n_compiles = TRACE_COUNTS["serve"], len(compiles)
    done = 0
    for k in WARM_KS:
        futs = [srv.submit(S, ds, seed=i)
                for i, (_, S, ds) in enumerate(reqs[:k])]
        srv.tick()
        done += sum(f.done() for f in futs)
    out["after_warm_traces"] = TRACE_COUNTS["serve"] - traces
    out["after_warm_compiles"] = len(compiles) - n_compiles
    out["after_warm_done"] = done

    # a host θ lands padded, each device holding only its block
    host = {k: np.asarray(v) for k, v in theta.items()}
    placed = place_theta(host, mesh((1, 4)), d)
    out["host_block"] = list(placed["M"].addressable_shards[0].data.shape)
    out["host_placed_equal"] = bool(np.array_equal(
        np.asarray(placed["M"])[..., :d], host["M"]))

    # the benchmark's θ, made laid out, equals θ made whole
    job = harness.load_module("jobs", "serve_mesh")
    cell = _bench_cell(harness)
    cfg = dict(cell["cfg"], theta_scale=0.1)
    key = harness.seed_key(SEED)
    whole = surfgen.make_theta(key, cfg, 0.1)
    split_theta = job.make_theta(key, cfg, 0.1, mesh((1, 4)))
    dd = whole["M"].shape[-1]
    out["bench_theta_equal"] = bool(
        np.array_equal(np.asarray(split_theta["M"])[..., :dd],
                       np.asarray(whole["M"]))
        and np.array_equal(np.asarray(split_theta["h"]),
                           np.asarray(whole["h"])))

    # the benchmark's job on a θ split 4 ways: sound, and with two θ
    # shards exchanged
    import repro.launch.mesh as RM
    RM.serve_mesh = lambda devices, cfg, task=None: mesh((1, 4))
    for fault in (None, "shard_swap"):
        r = job.run(_bench_cell(harness), SEED, 0.5, False, jax.devices(),
                    fault=fault)
        out[f"job_{fault}"] = {"checks": r["checks"],
                               "layout": r["info"]["layout"]}
    print(json.dumps(out))


def _rungs_run(srv, reqs, max_batch):
    """The rung (``slots`` of its ``serve.tick.call`` span) of one tick of
    each admitted count 1..``max_batch``, and whether the tick's
    ``ticks_by_slots`` count agrees."""
    import jax
    from repro.utils import spans
    rungs = []
    with tempfile.TemporaryDirectory() as trace_dir, \
            jax.profiler.trace(trace_dir):
        for k in range(1, max_batch + 1):
            before = dict(srv.metrics.ticks_by_slots)
            for i, (_, S, ds) in enumerate(reqs[:k]):
                srv.submit(S, ds, seed=i)
            t0 = time.perf_counter()
            assert srv.tick() == k
            (call,) = [r for r in spans.records()
                       if r.t0 >= t0 and r.name == "serve.tick.call"]
            B = call.attrs["slots"]
            counted = srv.metrics.ticks_by_slots[B] - before.get(B, 0)
            rungs.append(B if counted == 1 else None)
    return rungs


def _whole_fetch(srv, d):
    """The queued requests' results as the solver's whole output, fetched
    at once and then sliced: the batch ``tick`` would make of the queue,
    solved by the executable of the rung it would run."""
    import jax
    reqs = list(srv._queue)
    bucket, B = reqs[0].bucket, srv._rung(len(reqs))
    stacked, m, t = srv._batch(bucket, B, [r.arrays for r in reqs],
                               [r.mask for r in reqs],
                               [r.t_real for r in reqs])
    host = jax.device_get(srv._solver(bucket, B)(stacked[0], srv.theta,
                                                 *stacked[1:], m, t))
    res = [{key: v[i] for key, v in host.items() if key != "W"}
           for i in range(len(reqs))]
    for i, r in enumerate(reqs):
        res[i]["W"] = host["W"][i, :r.n_real, :d]
    return res


def _bench_cell(harness):
    cell = harness.load_cell("serve-leaf-femnist-poisson")
    cell["cfg"].update(n_agents=8, n_layers=3, feature_dim=16,
                       batch_per_agent=4, train_per_agent=9, test_per_agent=6)
    cell["traffic"].update(rate=20.0, federations=5, compare=12)
    return cell


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"),
                                           str(ROOT / "tests")]))
    code = "import test_serve_theta_split as t\nt._probe()\n"
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("split", SPLITS)
def test_split_server_matches_solve_federation(probe, split):
    """Final loss and accuracy of every request, θ split ``split`` ways,
    against ``solve_federation`` at HIGHEST precision."""
    assert probe[f"ref_gap_{split}"] < 5e-5


@pytest.mark.parametrize("split", SPLITS)
def test_split_server_matches_single_device_server(probe, split):
    assert probe[f"one_gap_{split}"] < 1e-5
    assert probe[f"w_shape_{split}"] == [12, probe["d"]]


@pytest.mark.parametrize("split", SPLITS)
def test_padded_columns_stay_exact_zeros(probe, split):
    cols = -(-probe["d"] // split) * split
    assert probe[f"cols_{split}"] == cols
    assert probe[f"pad_max_{split}"] == 0.0
    assert probe[f"theta_pad_max_{split}"] == 0.0
    assert probe[f"theta_block_{split}"][-1] == cols // split


def test_d_is_not_divisible_by_the_widest_split(probe):
    assert probe["d"] % 4 != 0


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("k", KS)
def test_served_results_are_the_solvers_output_bitwise(probe, layout, k):
    """With ``k`` admitted, every served result equals, bit for bit, the
    same executable's whole output fetched at once and sliced: the
    per-slot fetch changes no number, on one device, θ split 2 or 4 ways,
    or request slots over 4 devices."""
    assert probe[f"exact_{layout}_{k}"]


def test_request_split_server_matches_single_device_server(probe):
    """Request slots over a 4 × 1 mesh, θ whole on each device: the
    results of ticks of 1, 3 and 8 admitted against one device."""
    assert probe["one_gap_requests_4"] < 1e-5
    assert probe["w_shapes_requests_4"] == probe["w_shapes_one"]


def test_tick_after_warm_traces_and_compiles_nothing(probe):
    """``warm()`` traces the solver once a (bucket, rung): one bucket, and
    rungs 2 and 4 where the request axis splits 2 ways; ticks of 1 to 4
    admitted requests, every rung among them, then trace and compile
    nothing."""
    assert probe["warm_rungs"] == [2, 4]
    assert probe["warm_traces"] == 2
    assert probe["after_warm_done"] == sum(WARM_KS)
    assert probe["after_warm_traces"] == 0
    assert probe["after_warm_compiles"] == 0


@pytest.mark.parametrize("layout,k", [(layout, k)
                                      for layout, rungs in RUNGS.items()
                                      for k in range(1, len(rungs) + 1)])
def test_tick_runs_the_smallest_rung_that_holds_the_admitted(probe, layout,
                                                            k):
    """A tick of ``k`` admitted requests runs the smallest rung of
    ``shards`` × 2^j below ``max_batch``, then ``max_batch``, that holds
    them: as its ``serve.tick.call`` span's ``slots`` and its
    ``ticks_by_slots`` count both read."""
    assert probe[f"rungs_{layout}"][k - 1] == RUNGS[layout][k - 1]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_one_request_alone_matches_it_in_a_full_batch(probe, layout):
    """Request 0 served alone (the smallest rung) and among 8 (the full
    batch): the same numbers, within what the two executables' rounding
    allows."""
    assert probe[f"rung_gap_{layout}"] < 1e-5


def test_host_theta_is_placed_block_by_block(probe):
    assert probe["host_block"][-1] == -(-probe["d"] // 4)
    assert probe["host_placed_equal"]


def test_bench_theta_does_not_depend_on_the_layout(probe):
    assert probe["bench_theta_equal"]


@pytest.mark.parametrize("fault", [None, "shard_swap"])
def test_bench_job_on_a_split_theta(probe, fault):
    """The serving job's checks pass on a sound run and fail on
    ``loss_gap`` with two θ shards exchanged."""
    run = probe[f"job_{fault}"]
    assert run["layout"] == {"agent": 1, "theta": 4}
    checks = {name: (value, limit) for name, value, limit in run["checks"]}
    value, limit = checks["loss_gap"]
    assert (value <= limit) == (fault is None), checks
    assert checks["never_answered"][0] == 0


# ------------------------------------------------- layout from shapes alone
PAPER = SURFConfig(n_agents=100, n_layers=10, filter_taps=2, feature_dim=512,
                   n_classes=10, batch_per_agent=10)
FEMNIST = dataclasses.replace(PAPER, n_classes=62)


@pytest.mark.parametrize("cfg,gb,layout", [(PAPER, 2.124, (4, 1)),
                                           (FEMNIST, 47.769, (1, 4))])
def test_layout_from_theta_bytes(cfg, gb, layout):
    """The paper's θ serves whole on each of 4 chips; FEMNIST's needs
    all 4 to hold it, at a v5e's 15.75 GB."""
    theta_bytes = serve_theta_bytes(cfg)
    assert theta_bytes / 1e9 == pytest.approx(gb, abs=1e-3)
    assert serve_layout(theta_bytes, 4, V5E_LIMIT) == layout


def test_layout_without_a_known_limit_keeps_theta_whole():
    assert serve_layout(serve_theta_bytes(FEMNIST), 4, None) == (4, 1)


def test_layout_refuses_a_theta_no_split_holds():
    with pytest.raises(ValueError, match="does not fit"):
        serve_layout(serve_theta_bytes(FEMNIST), 2, V5E_LIMIT)


# ------------------------------------------- the benchmark's blocked reference
def test_blocked_reference_equals_whole_reference():
    """``reference_blocked`` (θ made a layer at a time, every request
    advanced through it) gives ``reference.make_solve``'s answers."""
    import jax.numpy as jnp
    sys.path.insert(0, str(BENCH))
    import harness
    import reference
    import reference_blocked
    import surfgen
    cfg = dict(_bench_cell(harness)["cfg"], theta_scale=0.1)
    key = harness.seed_key(SEED)
    build = surfgen.pool_maker(cfg, 3)
    pool = build(key, 0)
    feds = [(surfgen.mixing_matrix(cfg, [SEED, q]),
             {k: np.asarray(v[q]) for k, v in pool.items()})
            for q in range(3)]
    theta = surfgen.make_theta(key, cfg, 0.1)
    solve = reference.make_solve(cfg, jnp.float32)
    whole = [reference.serve_reference(cfg, theta, S, fed, 7 + q, solve)
             for q, (S, fed) in enumerate(feds)]
    blocked = reference_blocked.solve_requests(
        cfg, key, [(S, fed, 7 + q) for q, (S, fed) in enumerate(feds)],
        jnp.float32)
    for (W, loss, acc), (Wb, lossb, accb) in zip(whole, blocked):
        np.testing.assert_allclose(Wb, W, rtol=1e-6, atol=1e-6)
        assert lossb == pytest.approx(loss, rel=1e-6)
        assert accb == acc
