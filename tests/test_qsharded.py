"""Q/request-axis sharding: the Q-sharded train engine (pool + in-scan
snapshot eval placed over the agent-role axis, owner-masked psum select)
against the replicated trajectory, the 2-D seed×agent composition, the
Q-sharded async evaluator, and the mesh-sharded serve batch against the
solo reference solve.

Multi-device tests need ``XLA_FLAGS=--xla_force_host_platform_device_count
=8`` (the ``make test-sharded`` lane) and skip on a plain 1-device run;
the validation-error tests run in every lane.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import engine as E
from repro.configs.base import SURFConfig
from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.data import synthetic
from repro.launch.mesh import host_device_count, make_surf_mesh
from repro.serve import BucketSpec, FederationServer, serve_cache_key

NDEV = host_device_count()
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 devices: run via `make test-sharded` "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

# 16 agents, dense mixing — the Q axis (pool size 8) divides both the
# 8-way agent mesh and the 4-way agent sub-axis of the (2, 4) 2-D mesh.
CFG = SURFConfig(n_agents=16, n_layers=3, filter_taps=2, feature_dim=8,
                 n_classes=4, batch_per_agent=4, train_per_agent=8,
                 test_per_agent=4, eps=0.05, topology="ring", degree=2)
STEPS = 12
META_Q = 8
EVAL_Q = 4
EVAL_EVERY = 4


@pytest.fixture(scope="module")
def pools():
    mds = synthetic.make_meta_dataset(CFG, META_Q, seed=0)
    eval_ds = synthetic.make_meta_dataset(CFG, EVAL_Q, seed=777)
    return mds, eval_ds


def _train(mds, eval_ds, **kw):
    return surf.train_surf(CFG, mds, steps=STEPS, seed=0, log_every=STEPS,
                           eval_every=EVAL_EVERY, eval_datasets=eval_ds,
                           **kw)


def _max_delta(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------- Q-sharded train trajectory
@multi_device
def test_qsharded_train_matches_replicated(pools):
    """Pool + eval stack Q-sharded over an 8-way agent mesh: the masked
    psum select adds exact zeros, so theta and every in-scan snapshot
    match the replicated run — from ONE meta_step trace."""
    mds, eval_ds = pools
    ref_state, _, ref_snaps, _ = _train(mds, eval_ds)
    mesh = make_surf_mesh(1, 8)
    E.TRACE_COUNTS["meta_step"] = 0
    state, _, snaps, _ = _train(mds, eval_ds, mesh=mesh, q_sharded=True)
    assert E.TRACE_COUNTS["meta_step"] == 1
    assert _max_delta(state.theta, ref_state.theta) < 1e-6
    assert len(snaps) == len(ref_snaps) > 0
    for s, r in zip(snaps, ref_snaps):
        np.testing.assert_allclose(s["final_acc"], r["final_acc"],
                                   atol=1e-5)
        np.testing.assert_allclose(s["final_loss"], r["final_loss"],
                                   atol=1e-5)


@multi_device
def test_q_select_collective_bytes_flat_in_q():
    """Per-meta-step collective bytes of the Q-sharded scan engine stay
    flat from Q to 2Q to 4Q (the owner-masked psum select moves one
    dataset a step), while the naive dynamic-index select on the same
    sharded pool all-gathers the pool and grows with Q."""
    from repro.launch.surf_dryrun import q_scan_collective_bytes
    cfg = SURFConfig(n_agents=32, n_layers=4, filter_taps=2, feature_dim=16,
                     n_classes=8, batch_per_agent=6, train_per_agent=12,
                     test_per_agent=6, eps=0.05, topology="ring", degree=2)
    q, eval_q, agent_shards = 16, 8, 8
    mesh = make_surf_mesh(1, agent_shards, n_agents=cfg.n_agents)
    _, S = surf.make_problem(cfg, seed=0)
    masked, naive = [], []
    for n_q in (q, 2 * q, 4 * q):
        masked.append(q_scan_collective_bytes(cfg, S, mesh, n_q, steps=4,
                                              eval_q=eval_q)[0])
        naive.append(q_scan_collective_bytes(cfg, S, mesh, n_q, steps=4,
                                             eval_q=eval_q,
                                             naive_select=True)[0])
    assert masked[0] > 0 and naive[0] > 0
    growth = masked[-1] / masked[0]
    assert growth <= 1.05, masked
    assert naive[-1] / naive[0] > growth, naive


@multi_device
def test_qsharded_seed_engine_2d_mesh(pools):
    """Seed-batched engine on a (seed=2, agent=4) mesh with the pool AND
    eval stack Q-sharded over the agent sub-axis: per-seed rows match
    the replicated seed-batched run."""
    mds, eval_ds = pools
    seeds = (0, 1)
    ref_states, _, ref_snaps, _ = _train(mds, eval_ds, seeds=seeds)
    mesh = make_surf_mesh(2, 4, n_seeds=len(seeds))
    states, _, snaps, _ = _train(mds, eval_ds, seeds=seeds, mesh=mesh,
                                 q_sharded=True)
    assert _max_delta(states.theta, ref_states.theta) < 1e-6
    assert len(snaps) == len(ref_snaps) > 0
    for s, r in zip(snaps, ref_snaps):
        assert s["final_acc"].shape == (len(seeds),)
        np.testing.assert_allclose(s["final_acc"], r["final_acc"],
                                   atol=1e-5)


@multi_device
def test_evaluate_async_q_sharded(pools):
    """The async evaluator under a Q-sharded pool placement matches the
    unsharded run (same fold_in mask stream per dataset index)."""
    mds, eval_ds = pools
    state, _, _, S = _train(mds, eval_ds)
    ref = surf.evaluate_async(CFG, state, S, eval_ds, n_async=4, seed=3)
    sharded = surf.evaluate_async(CFG, state, S, eval_ds, n_async=4,
                                  seed=3, mesh=make_surf_mesh(1, 8))
    for k in ("final_acc", "final_loss"):
        np.testing.assert_allclose(sharded[k], ref[k], rtol=1e-5,
                                   atol=1e-5)


# --------------------------------------------------- validation errors
def test_qsharded_requires_mesh():
    mds = synthetic.make_meta_dataset(CFG, META_Q, seed=0)
    with pytest.raises(ValueError, match="q_sharded"):
        surf.train_surf(CFG, mds, steps=2, log_every=0, q_sharded=True)


def test_qsharded_rejects_python_engine():
    mds = synthetic.make_meta_dataset(CFG, META_Q, seed=0)
    with pytest.raises(ValueError, match="q_sharded"):
        surf.train_surf(CFG, mds, steps=2, log_every=0, q_sharded=True,
                        engine="python")


def test_qsharded_rejects_agent_sharded_mixers():
    """Ring/halo mixers need the pool's AGENT dim on the agent axis —
    Q-sharding it instead must be a loud error, not silent wrongness."""
    mds = synthetic.make_meta_dataset(CFG, META_Q, seed=0)
    with pytest.raises(ValueError, match="q_sharded"):
        surf.train_surf(CFG, mds, steps=2, log_every=0, q_sharded=True,
                        mesh=make_surf_mesh(1, 1), mix="ring")


def test_seed_qsharded_requires_2d_mesh():
    mds = synthetic.make_meta_dataset(CFG, META_Q, seed=0)
    with pytest.raises(ValueError, match="2-D"):
        surf.train_surf(CFG, mds, steps=2, log_every=0, seeds=(0, 1),
                        q_sharded=True, mesh=make_surf_mesh(1, 1))


def test_serve_cache_key_carries_mesh_fingerprint():
    """A request-sharded serve executable must never collide with the
    unsharded one for the same bucket."""
    from repro.serve.buckets import Bucket
    b = Bucket(8, 4)
    k_plain = serve_cache_key(SMOKE, b, 4, "relu")
    k_mesh = serve_cache_key(SMOKE, b, 4, "relu",
                             mesh=make_surf_mesh(1, 1))
    assert k_plain != k_mesh


# ------------------------------------------------ mesh-sharded serving
def _cohort(cfg, n, t, seed):
    cfg_r = dataclasses.replace(cfg, n_agents=n, test_per_agent=t)
    _, S = surf.make_problem(cfg_r, seed=seed)
    ds = synthetic.sample_dataset(cfg_r, seed=1000 + seed)
    return cfg_r, np.asarray(S), ds


@pytest.fixture(scope="module")
def served():
    mds = synthetic.make_meta_dataset(SMOKE, 3, seed=0)
    state, _, S = surf.train_surf(SMOKE, mds, steps=8, seed=0, log_every=0)
    return state, S


@multi_device
@pytest.mark.parametrize("depth", ["fixed", "adaptive"])
@pytest.mark.parametrize("mix", [None, "pallas"])
def test_sharded_serve_matches_solo_solve(served, mix, depth):
    """Request axis sharded over 8 devices (zero collectives — each
    device solves its block of slots): every ragged request matches the
    single-cohort ``solve_federation`` reference, including partially
    full batches riding as masked empty slots. The adaptive-depth solver
    runs its early-exit loop per device and must realize the reference
    depth."""
    state, _ = served
    cfg = (dataclasses.replace(SMOKE, exit_threshold=0.05, min_layers=2)
           if depth == "adaptive" else SMOKE)
    srv = FederationServer(cfg, state.theta, mix=mix, max_batch=8,
                           buckets=BucketSpec(agent_sizes=(8, 16),
                                              row_sizes=(4, 8)),
                           depth=depth, mesh=make_surf_mesh(1, 8))
    reqs = [_cohort(cfg, n, t, seed=50 + i)
            for i, (n, t) in enumerate([(6, 4), (8, 4), (12, 4), (16, 4),
                                        (14, 4), (10, 4)])]
    futs = [srv.submit(S, ds, seed=i) for i, (_, S, ds) in enumerate(reqs)]
    srv.drain()
    tol = 5e-4 if mix == "pallas" else 5e-5
    for i, ((cfg_r, S, ds), fut) in enumerate(zip(reqs, futs)):
        ref = surf.solve_federation(cfg_r, state, S, ds, seed=i,
                                    depth=depth)
        res = fut.result()
        assert abs(float(res["final_loss"] - ref["final_loss"])) < tol
        assert abs(float(res["final_acc"] - ref["final_acc"])) < tol
        if depth == "adaptive":
            assert int(res["depth"]) == round(float(ref["depth"]))


@multi_device
def test_sharded_serve_rejects_indivisible_batch(served):
    state, _ = served
    with pytest.raises(ValueError, match="divide"):
        FederationServer(SMOKE, state.theta, max_batch=6,
                         buckets=BucketSpec(agent_sizes=(8,),
                                            row_sizes=(4,)),
                         mesh=make_surf_mesh(1, 8))
