"""Convergence-adaptive depth (the early-exit while-loop solver):
exit_threshold=0 parity with the fixed-L forward, min_layers flooring,
threshold monotonicity, eval/serve trace economy, cache-key anatomy,
batched-serve parity against the solo adaptive solve (dense AND pallas
mix, padded AND exact-fit), probe-pad inertness, and the depth
telemetry the serving metrics grow.

A trained model is shared module-wide (one short meta-training run);
the multi-device variant runs only in the sharded lane
(``make test-sharded``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.data import synthetic
from repro.launch.mesh import host_device_count, make_agent_mesh
from repro.serve import Bucket, BucketSpec, FederationServer, serve_cache_key

CFG = SMOKE                      # n=8, L=4, thr=0 (early exit disabled)
STEPS = 8
BUCKETS = BucketSpec(agent_sizes=(8, 16), row_sizes=(4, 8))

NDEV = host_device_count()
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 devices: run via `make test-sharded` "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def trained():
    mds = synthetic.make_meta_dataset(CFG, 3, seed=0)
    state, _, S = surf.train_surf(CFG, mds, steps=STEPS, seed=0,
                                  log_every=0)
    return state, np.asarray(S)


def _cohort(n, t, seed):
    cfg_r = dataclasses.replace(CFG, n_agents=n, test_per_agent=t)
    _, S = surf.make_problem(cfg_r, seed=seed)
    ds = synthetic.sample_dataset(cfg_r, seed=1000 + seed)
    return cfg_r, np.asarray(S), ds


def _featurized(trained, cfg, seed=3):
    state, S = trained
    ds = synthetic.sample_dataset(cfg, seed=500)
    batch = {k: jnp.asarray(v) for k, v in ds.items()}
    key = jax.random.fold_in(jax.random.PRNGKey(1000 + seed), 0)
    task = resolve_task(cfg)
    W0, Xl, Yl = U.featurize_cohort(key, batch, cfg, task=task)
    Xp, Yp = U.probe_batch(batch, cfg)
    return state, jnp.asarray(S), W0, Xl, Yl, Xp, Yp


# ------------------------------------------------------- unroll parity
def test_threshold_zero_runs_all_layers_and_matches_fixed(trained):
    """exit_threshold=0 statically disables the exit: depth == L and
    W_L allclose to udgd_forward on the SAME pre-sampled batch stack."""
    state, S, W0, Xl, Yl, Xp, Yp = _featurized(trained, CFG)
    W_fix, _ = U.udgd_forward(state.theta, S, W0, Xl, Yl, CFG)
    W_ad, depth = U.udgd_forward_adaptive(state.theta, S, W0, Xl, Yl,
                                          Xp, Yp, CFG)
    assert int(depth) == CFG.n_layers
    np.testing.assert_allclose(np.asarray(W_ad), np.asarray(W_fix),
                               rtol=1e-5, atol=1e-6)


def test_huge_threshold_exits_at_min_layers(trained):
    """1 - thr < 0 makes the certificate fire on ANY ratio — the floor
    is min_layers exactly."""
    cfg = dataclasses.replace(CFG, exit_threshold=10.0, min_layers=2)
    state, S, W0, Xl, Yl, Xp, Yp = _featurized(trained, cfg)
    _, depth = U.udgd_forward_adaptive(state.theta, S, W0, Xl, Yl,
                                       Xp, Yp, cfg)
    assert int(depth) == 2


def test_depth_weakly_decreases_in_threshold(trained):
    """The W trajectory is threshold-independent up to the exit point,
    so a larger threshold can only fire earlier or at the same layer."""
    depths = []
    for thr in [0.01, 0.1, 10.0]:
        cfg = dataclasses.replace(CFG, exit_threshold=thr, min_layers=1)
        state, S, W0, Xl, Yl, Xp, Yp = _featurized(trained, cfg)
        _, d = U.udgd_forward_adaptive(state.theta, S, W0, Xl, Yl,
                                       Xp, Yp, cfg)
        depths.append(int(d))
    assert depths == sorted(depths, reverse=True)
    assert depths[-1] == 1


# --------------------------------------------------- evaluate_surf path
def test_evaluate_surf_adaptive_thr0_matches_fixed_final_row(trained):
    state, S = trained
    pool = synthetic.make_meta_dataset(CFG, 3, seed=9)
    fixed = surf.evaluate_surf(CFG, state, S, pool, seed=5)
    r = surf.evaluate_surf(CFG, state, S, pool, seed=5, depth="adaptive")
    assert r["depth"] == float(CFG.n_layers)
    np.testing.assert_allclose(r["final_loss"], fixed["final_loss"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(r["final_acc"], fixed["final_acc"],
                               rtol=1e-5, atol=1e-5)


def test_adaptive_trace_economy_per_threshold(trained):
    state, S = trained
    pool = synthetic.make_meta_dataset(CFG, 2, seed=10)
    cfg_a = dataclasses.replace(CFG, exit_threshold=0.17)
    cfg_b = dataclasses.replace(CFG, exit_threshold=0.19)
    base = E.TRACE_COUNTS["adaptive"]
    surf.evaluate_surf(cfg_a, state, S, pool, depth="adaptive")
    surf.evaluate_surf(cfg_a, state, S, pool, seed=3, depth="adaptive")
    assert E.TRACE_COUNTS["adaptive"] - base == 1   # re-eval: cache hit
    surf.evaluate_surf(cfg_b, state, S, pool, depth="adaptive")
    assert E.TRACE_COUNTS["adaptive"] - base == 2   # new threshold


def test_depth_argument_validation(trained):
    state, S = trained
    pool = synthetic.make_meta_dataset(CFG, 2, seed=11)
    with pytest.raises(ValueError, match="depth must be one of"):
        surf.evaluate_surf(CFG, state, S, pool, depth="deep")
    bad = dataclasses.replace(CFG, min_layers=CFG.n_layers + 1)
    with pytest.raises(ValueError, match="min_layers"):
        surf.evaluate_surf(bad, state, S, pool, depth="adaptive")


@multi_device
def test_adaptive_eval_q_sharded_matches_single_device(trained):
    """The while-loop evaluator under the Q-sharded stacked pool (the
    vmap lifts cond to an all-lanes any) matches the unsharded run."""
    state, S = trained
    pool = synthetic.make_meta_dataset(CFG, 8, seed=12)
    cfg = dataclasses.replace(CFG, exit_threshold=0.1, min_layers=2)
    ref = surf.evaluate_surf(cfg, state, S, pool, depth="adaptive")
    mesh = make_agent_mesh(8)
    sharded = surf.evaluate_surf(cfg, state, S, pool, depth="adaptive",
                                 mesh=mesh)
    assert sharded["depth"] == ref["depth"]
    np.testing.assert_allclose(sharded["final_acc"], ref["final_acc"],
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- cache anatomy
def test_fixed_engine_keys_ignore_exit_fields():
    """Threshold sweeps must share the fixed-depth executables: the key
    normalizer scrubs the exit knobs from cfg."""
    k0 = E._engine_cache_key(CFG, "eval", "relu", None)
    k1 = E._engine_cache_key(
        dataclasses.replace(CFG, exit_threshold=0.3, min_layers=2,
                            probe_size=8), "eval", "relu", None)
    assert k0 == k1


def test_adaptive_variants_key_apart_per_threshold():
    cfg_a = dataclasses.replace(CFG, exit_threshold=0.1)
    cfg_b = dataclasses.replace(CFG, exit_threshold=0.2)
    va = E.adaptive_variant(cfg_a, "eval")
    vb = E.adaptive_variant(cfg_b, "eval")
    assert va != vb
    assert E._engine_cache_key(cfg_a, va, "relu", None) != \
        E._engine_cache_key(cfg_b, vb, "relu", None)


def test_serve_cache_key_depth_separation():
    """Fixed serve keys ignore the exit knobs; adaptive keys carry them
    in the variant (one executable per threshold)."""
    cfg_t = dataclasses.replace(CFG, exit_threshold=0.1)
    b = Bucket(8, 4)
    assert serve_cache_key(cfg_t, b, 4, "relu") == \
        serve_cache_key(CFG, b, 4, "relu")
    ka = serve_cache_key(cfg_t, b, 4, "relu", depth="adaptive")
    kb = serve_cache_key(dataclasses.replace(CFG, exit_threshold=0.2),
                         b, 4, "relu", depth="adaptive")
    assert ka != kb != serve_cache_key(CFG, b, 4, "relu")


# ------------------------------------------------------- serving parity
@pytest.mark.parametrize("mix", [None, "pallas"])
def test_batched_serve_matches_solo_adaptive_solves(trained, mix):
    """Mixed easy/hard requests batched through ONE early-exit while
    loop: each request's depth and metrics equal its SOLO adaptive
    solve — fired requests freeze, active ones keep stepping, padding
    never flips a certificate."""
    state, _ = trained
    cfg = dataclasses.replace(CFG, exit_threshold=0.2, min_layers=1)
    srv = FederationServer(cfg, state.theta, mix=mix, buckets=BUCKETS,
                           max_batch=4, depth="adaptive")
    reqs = []
    for n, seed in [(8, 0), (6, 1), (8, 2)]:    # exact-fit AND padded
        cfg_r, S, ds = _cohort(n, 4, seed=30 + seed)
        cfg_r = dataclasses.replace(cfg_r, exit_threshold=0.2,
                                    min_layers=1)
        reqs.append((cfg_r, S, ds, srv.submit(S, ds, seed=seed)))
    srv.drain()
    tol = 5e-5 if mix == "pallas" else 1e-5
    for seed, (cfg_r, S, ds, fut) in enumerate(reqs):
        ref = surf.solve_federation(cfg_r, state, S, ds, seed=seed,
                                    depth="adaptive",
                                    mix_fn=srv.mix_fn)
        res = fut.result()
        assert int(res["depth"]) == int(ref["depth"])
        np.testing.assert_allclose(res["final_loss"], ref["final_loss"],
                                   atol=tol, rtol=tol)
        np.testing.assert_allclose(res["final_acc"], ref["final_acc"],
                                   atol=tol, rtol=tol)


def test_junk_in_probe_pad_region_is_inert(trained):
    """Poisoning the padded agents' rows — INCLUDING the probe split —
    must change neither the result nor the realized depth
    (masked_grad_norm zeroes padded grads exactly)."""
    state, _ = trained
    cfg = dataclasses.replace(CFG, exit_threshold=0.2, min_layers=1)
    cfg_r, S, ds = _cohort(6, 4, seed=44)
    cfg_r = dataclasses.replace(cfg_r, exit_threshold=0.2, min_layers=1)
    srv = FederationServer(cfg, state.theta, buckets=BUCKETS,
                           max_batch=4, depth="adaptive")
    fut = srv.submit(S, ds, seed=1)
    req = srv._queue[0]
    arrs = list(req.arrays)
    arrs[1] = arrs[1].at[6:].set(1e6)       # W0 pad rows
    arrs[2] = arrs[2].at[:, 6:].set(-3e5)   # layer-batch pad rows
    arrs[6] = arrs[6].at[6:].set(4e5)       # probe X pad rows
    req.arrays = tuple(arrs)
    srv.drain()
    ref = surf.solve_federation(cfg_r, state, S, ds, seed=1,
                                depth="adaptive")
    res = fut.result()
    assert int(res["depth"]) == int(ref["depth"])
    np.testing.assert_allclose(res["final_acc"], ref["final_acc"],
                               atol=1e-5, rtol=1e-5)


def test_adaptive_serve_requires_probe_rows(trained):
    state, _ = trained
    cfg = dataclasses.replace(CFG, exit_threshold=0.2,
                              probe_size=CFG.train_per_agent + 1)
    srv = FederationServer(cfg, state.theta, buckets=BUCKETS,
                           max_batch=2, depth="adaptive")
    _, S, ds = _cohort(8, 4, seed=50)
    with pytest.raises(ValueError, match="probe"):
        srv.submit(S, ds)


def test_depth_rejected_at_server_construction(trained):
    state, _ = trained
    with pytest.raises(ValueError, match="depth must be"):
        FederationServer(CFG, state.theta, depth="variable")
    with pytest.raises(ValueError, match="max_wait_ticks"):
        FederationServer(CFG, state.theta, max_wait_ticks=0)


# ------------------------------------------------------ depth telemetry
def test_serve_metrics_grow_depth_histogram(trained):
    state, _ = trained
    cfg = dataclasses.replace(CFG, exit_threshold=10.0, min_layers=2)
    srv = FederationServer(cfg, state.theta, buckets=BUCKETS,
                           max_batch=4, depth="adaptive")
    for i in range(3):
        _, S, ds = _cohort(8, 4, seed=60 + i)
        srv.submit(S, ds, seed=i)
    srv.drain()
    s = srv.metrics.summary()
    # thr=10 fires at min_layers=2 for every request: one histogram bin
    assert s["depth_hist"] == {"2": 3}
    assert s["mean_depth"] == 2.0
    # per-request: 1 - (3*2)/(3*4); per-batch: the tick ran 2 of 4 layers
    assert s["request_flops_saved"] == pytest.approx(0.5)
    assert s["batch_flops_saved"] == pytest.approx(0.5)


def test_fixed_serve_metrics_have_no_depth_fields(trained):
    state, _ = trained
    srv = FederationServer(CFG, state.theta, buckets=BUCKETS, max_batch=4)
    _, S, ds = _cohort(8, 4, seed=70)
    srv.submit(S, ds)
    srv.drain()
    s = srv.metrics.summary()
    assert "depth_hist" not in s and "mean_depth" not in s
