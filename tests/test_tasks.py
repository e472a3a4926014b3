"""The task abstraction layer (``core.tasks``): classification-via-Task
bit-exact parity with the legacy path, the sparse-recovery (federated
LASSO) task through the SAME engine, task-tagged cache-key separation,
and the RSDUN robust descent constraints.

Multi-device tests (sparse recovery through the ring/scheduled-halo
mixers) carry the same skip marker as ``tests/test_sharded_engine.py``
and run in the ``make test-sharded`` lane.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.configs.base import (ClassificationTaskConfig,
                                SparseRecoveryTaskConfig, SURFConfig)
from repro.configs.surf_paper import SMOKE, SPARSE_SMOKE
from repro.core import baselines as B
from repro.core import constraints as C
from repro.core import surf
from repro.core import unroll as U
from repro.core.tasks import (ClassificationTask, SparseRecoveryTask,
                              classification_task, resolve_task,
                              signal_nmse, soft_threshold,
                              sparse_recovery_task, support_f1)
from repro.core.tasks import classification as T
from repro.data import synthetic
from repro.launch.mesh import host_device_count
from repro.launch.surf_dryrun import surf_batch_specs

NDEV = host_device_count()
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 devices: run via `make test-sharded` "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

CFG = SMOKE
SCFG = SPARSE_SMOKE
STEPS = 12


@pytest.fixture(scope="module")
def mds():
    return synthetic.make_meta_dataset(CFG, 4, seed=0)


@pytest.fixture(scope="module")
def sparse_mds():
    task = sparse_recovery_task(SCFG)
    return task.synth_datasets(SCFG, 4, seed=0)


def _tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _hist_equal(ha, hb):
    assert [h["step"] for h in ha] == [h["step"] for h in hb]
    for ra, rb in zip(ha, hb):
        assert ra.keys() == rb.keys()
        for k in ra:
            if k == "step":
                continue
            np.testing.assert_array_equal(np.asarray(ra[k]),
                                          np.asarray(rb[k]))


# -------------------------------------------------- resolution / config
def test_resolve_task_default_is_legacy_classification():
    task = resolve_task(CFG)
    assert isinstance(task, ClassificationTask)
    assert task.feat_dim == CFG.feature_dim
    assert task.n_classes == CFG.n_classes
    assert task.dim == T.head_dim(CFG.feature_dim, CFG.n_classes)
    assert CFG.head_dim == task.dim


def test_resolve_task_dispatches_cfg_task_and_explicit_wins():
    task = resolve_task(SCFG)
    assert isinstance(task, SparseRecoveryTask)
    assert task.signal_dim == SCFG.task.signal_dim
    assert SCFG.head_dim == task.dim == SCFG.task.signal_dim
    explicit = SparseRecoveryTask(signal_dim=7)
    assert resolve_task(CFG, explicit) is explicit
    cfg_cls = dataclasses.replace(
        CFG, task=ClassificationTaskConfig(feature_dim=5, n_classes=3))
    t2 = resolve_task(cfg_cls)
    assert (t2.feat_dim, t2.n_classes) == (5, 3)

    @dataclasses.dataclass(frozen=True)
    class BogusTC:
        kind: str = "nope"
    with pytest.raises(ValueError, match="unknown task kind"):
        resolve_task(dataclasses.replace(CFG, task=BogusTC()))


def test_task_is_hashable_static_arg():
    t1 = ClassificationTask(feat_dim=8, n_classes=4)
    t2 = ClassificationTask(feat_dim=8, n_classes=4)
    assert t1 == t2 and hash(t1) == hash(t2)
    assert len({t1, t2, SparseRecoveryTask()}) == 2


# ------------------------------------------- classification task parity
def test_classification_task_functions_match_legacy(key):
    task = classification_task(CFG)
    n, b, F_, C_ = CFG.n_agents, 5, CFG.feature_dim, CFG.n_classes
    k1, k2, k3 = jax.random.split(key, 3)
    W = jax.random.normal(k1, (n, task.dim))
    X = jax.random.normal(k2, (n, b, F_))
    Y = jax.random.randint(k3, (n, b), 0, C_)
    np.testing.assert_array_equal(
        task.fl_loss(W, X, Y), T.fl_loss(W, X, Y, F_, C_))
    np.testing.assert_array_equal(
        task.fl_metric(W, X, Y), T.fl_accuracy(W, X, Y, F_, C_))
    np.testing.assert_array_equal(
        task.fl_grad(W, X, Y), T.fl_grad(W, X, Y, F_, C_))
    np.testing.assert_array_equal(
        task.grad_norm(W, X, Y), T.grad_norm(W, X, Y, F_, C_))
    np.testing.assert_array_equal(
        task.batch_vector(X, Y), U.batch_vector(X, Y, C_))
    w0_task = task.init_state(key, CFG)
    np.testing.assert_array_equal(w0_task, U.sample_w0(key, CFG))


def test_train_surf_via_task_is_bit_exact(mds):
    """Tentpole acceptance: ``train_surf(task=classification_task(cfg))``
    reproduces the default run bit for bit — state leaves, history and
    the downstream evaluator."""
    st0, hist0, S0 = surf.train_surf(CFG, mds, steps=STEPS, log_every=4)
    st1, hist1, S1 = surf.train_surf(CFG, mds, steps=STEPS, log_every=4,
                                     task=classification_task(CFG))
    np.testing.assert_array_equal(np.asarray(S0), np.asarray(S1))
    _tree_equal(st0, st1)
    _hist_equal(hist0, hist1)
    ev0 = surf.evaluate_surf(CFG, st0, S0, mds, seed=0)
    ev1 = surf.evaluate_surf(CFG, st1, S1, mds, seed=0,
                             task=classification_task(CFG))
    for k in ev0:
        np.testing.assert_array_equal(ev0[k], ev1[k])


def test_snapshots_via_task_are_bit_exact(mds):
    eval_ds = synthetic.make_meta_dataset(CFG, 2, seed=7)
    out0 = surf.train_surf(CFG, mds, steps=8, log_every=0, eval_every=4,
                           eval_datasets=eval_ds)
    out1 = surf.train_surf(CFG, mds, steps=8, log_every=0, eval_every=4,
                           eval_datasets=eval_ds,
                           task=classification_task(CFG))
    _tree_equal(out0[0], out1[0])
    assert [s["step"] for s in out0[2]] == [s["step"] for s in out1[2]]
    for sa, sb in zip(out0[2], out1[2]):
        for k in sa:
            np.testing.assert_array_equal(np.asarray(sa[k]),
                                          np.asarray(sb[k]))


# ------------------------------------------------ cache-key separation
def test_engine_cache_keys_separate_by_task_tag():
    k_default = E._engine_cache_key(CFG, "train", "relu", None)
    k_explicit = E._engine_cache_key(CFG, "train", "relu", None,
                                     task=classification_task(CFG))
    assert k_default == k_explicit          # same cache_tag -> one engine
    k_sparse = E._engine_cache_key(CFG, "train", "relu", None,
                                   task=SparseRecoveryTask(signal_dim=16))
    assert k_sparse != k_default
    assert k_sparse[-1][0] == "sparse-recovery"
    # two sparse tasks differing only in rho are different executables
    k_rho = E._engine_cache_key(CFG, "train", "relu", None,
                                task=SparseRecoveryTask(signal_dim=16,
                                                        rho=0.5))
    assert k_rho != k_sparse


def test_sparse_engine_traces_once(sparse_mds):
    E.TRACE_COUNTS["meta_step"] = 0
    surf.train_surf(SCFG, sparse_mds, steps=4, log_every=0)
    assert E.TRACE_COUNTS["meta_step"] == 1
    surf.train_surf(SCFG, sparse_mds, steps=4, log_every=0)
    assert E.TRACE_COUNTS["meta_step"] == 1   # cache hit across runs


# --------------------------------------------- sparse recovery e2e
def test_sparse_dataset_layout():
    task = sparse_recovery_task(SCFG)
    ds, truths = synthetic.make_sparse_meta_dataset(SCFG, 3, task, seed=0,
                                                    return_truth=True)
    assert len(ds) == 3 and truths.shape == (3, task.signal_dim)
    d = ds[0]
    n, p = SCFG.n_agents, task.signal_dim
    assert d["Xtr"].shape == (n, SCFG.train_per_agent, p)
    assert d["Ytr"].shape == (n, SCFG.train_per_agent)
    assert d["Xtr"].dtype == np.float32 and d["Ytr"].dtype == np.float32
    # each problem's truth is k-sparse
    assert (np.abs(truths) > 0).sum(1).tolist() == [task.sparsity] * 3


def test_sparse_recovery_trains_through_engine(sparse_mds):
    """Tentpole acceptance (dense path): the federated-LASSO task trains
    through the identical engine — loss decreases, the generic metric
    slots carry NMSE, and the evaluator runs task-aware."""
    state, hist, S = surf.train_surf(SCFG, sparse_mds, steps=40,
                                     log_every=4)
    losses = [h["test_loss"] for h in hist]
    assert all(np.isfinite(losses))
    # each logged point is one noisy meta-step: compare window means
    third = len(losses) // 3
    assert np.mean(losses[-third:]) < np.mean(losses[:third])
    # "acc" slots generically carry the task metric (NMSE, lower=better)
    assert np.isfinite(hist[-1]["test_acc"])
    ev = surf.evaluate_surf(SCFG, state, S, sparse_mds, seed=0,
                            task=sparse_recovery_task(SCFG))
    assert ev["acc_per_layer"].shape == (SCFG.n_layers,)
    assert np.isfinite(ev["final_acc"])


def test_sparse_python_engine_matches_scan(sparse_mds):
    st_s, _, S = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0)
    st_p, _, _ = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0,
                                 engine="python")
    for x, y in zip(jax.tree_util.tree_leaves(st_s),
                    jax.tree_util.tree_leaves(st_p)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


def test_sparse_seed_batched_matches_sequential(sparse_mds):
    seeds = [0, 1]
    states, hist, S_stack = surf.train_surf(SCFG, sparse_mds, steps=8,
                                            seeds=seeds, log_every=4)
    for i, s in enumerate(seeds):
        st_i, hist_i, S_i = surf.train_surf(SCFG, sparse_mds, steps=8,
                                            seed=s, log_every=4)
        np.testing.assert_array_equal(np.asarray(S_stack[i]),
                                      np.asarray(S_i))
        for x, y in zip(jax.tree_util.tree_leaves(
                            E.state_for_seed(states, i)),
                        jax.tree_util.tree_leaves(st_i)):
            # vmapped-vs-sequential float32 reassociation tolerance
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-4, rtol=1e-4)


def test_sparse_baselines_run(sparse_mds):
    task = sparse_recovery_task(SCFG)
    _, S = surf.make_problem(SCFG, seed=0)
    W0 = U.sample_w0(jax.random.PRNGKey(0), SCFG, task=task)
    key = jax.random.PRNGKey(1)
    out = B.run_dgd(S, W0, sparse_mds[0], key, SCFG, rounds=30, lr=1e-1,
                    task=task)
    assert np.isfinite(out["loss"]).all()
    assert out["loss"][-1] < out["loss"][0]
    out = B.run_fedavg(W0, sparse_mds[0], key, SCFG, rounds=5,
                       participate=SCFG.n_agents, task=task)
    assert np.isfinite(out["loss"]).all()


# ------------------------------------------------------- sparse helpers
def test_soft_threshold_and_support_f1():
    w = jnp.array([0.5, -0.05, 0.0, -2.0])
    out = np.asarray(soft_threshold(w, 0.1))
    np.testing.assert_allclose(out, [0.4, 0.0, 0.0, -1.9], atol=1e-7)
    w_star = jnp.array([1.0, 0.0, 0.0, -1.0])
    assert float(support_f1(w, w_star, tau=0.1)) == 1.0
    assert float(support_f1(jnp.zeros(4), w_star)) == 0.0
    W = jnp.stack([w_star, w_star])
    assert float(signal_nmse(W, w_star)) == 0.0


# --------------------------------------------- robust (RSDUN) constraints
def _grad_norm_inputs(key, cfg):
    task = resolve_task(cfg)
    L_, n, b = cfg.n_layers, cfg.n_agents, cfg.batch_per_agent
    k1, k2, k3 = jax.random.split(key, 3)
    W_all = jax.random.normal(k1, (L_ + 1, n, task.dim))
    Xl = jax.random.normal(k2, (L_, n, b, cfg.feature_dim))
    Yl = jax.random.randint(k3, (L_, n, b), 0, cfg.n_classes)
    return W_all, Xl, Yl


def test_robust_slack_equals_nominal_at_sigma_zero(key):
    """Satellite acceptance: at σ=0 the robust slack equals (hence
    upper-bounds) the nominal slack — same dual-ascent loop either way."""
    W_all, Xl, Yl = _grad_norm_inputs(key, CFG)
    g_nom = C.layer_grad_norms(W_all, Xl, Yl, CFG)
    g_rob = C.robust_layer_grad_norms(W_all, Xl, Yl, CFG, key)
    np.testing.assert_array_equal(np.asarray(g_rob), np.asarray(g_nom))
    np.testing.assert_array_equal(
        np.asarray(C.robust_slacks(g_rob, g_nom, CFG.eps)),
        np.asarray(C.slacks(g_nom, CFG.eps)))


def test_robust_slack_upper_bounds_nominal(key):
    cfg = dataclasses.replace(CFG, robust_sigma=0.5, robust_samples=3)
    W_all, Xl, Yl = _grad_norm_inputs(key, cfg)
    g_nom = C.layer_grad_norms(W_all, Xl, Yl, cfg)
    g_rob = C.robust_layer_grad_norms(W_all, Xl, Yl, cfg, key)
    assert (np.asarray(g_rob) >= np.asarray(g_nom)).all()
    rs = np.asarray(C.robust_slacks(g_rob, g_nom, cfg.eps))
    ns = np.asarray(C.slacks(g_nom, cfg.eps))
    assert (rs >= ns - 1e-7).all()


def test_robust_training_runs_and_default_stream_untouched(mds):
    """robust_sigma=0 must not perturb the default RNG stream (the robust
    branch is trace-time); robust_sigma>0 trains finite through the same
    scan."""
    st0, hist0, _ = surf.train_surf(CFG, mds, steps=6, log_every=3)
    cfg_r0 = dataclasses.replace(CFG, robust_sigma=0.0, robust_samples=4)
    st1, hist1, _ = surf.train_surf(cfg_r0, mds, steps=6, log_every=3)
    _tree_equal(st0, st1)
    cfg_rob = dataclasses.replace(CFG, robust_sigma=0.1, robust_samples=2)
    st2, hist2, _ = surf.train_surf(cfg_rob, mds, steps=6, log_every=3)
    assert np.isfinite(hist2[-1]["test_loss"])
    # robust run takes a different trajectory than the nominal one
    assert not np.array_equal(np.asarray(st2.theta["h"]),
                              np.asarray(st0.theta["h"]))


def test_robust_flag_separates_cache_keys():
    cfg_rob = dataclasses.replace(CFG, robust_sigma=0.1)
    assert (E._engine_cache_key(cfg_rob, "train", "relu", None)
            != E._engine_cache_key(CFG, "train", "relu", None))


# ---------------------------------------------------- batch specs / misc
def test_surf_batch_specs_are_task_aware():
    spec_c = surf_batch_specs(CFG)
    assert spec_c["Xtr"].shape[-1] == CFG.feature_dim
    assert spec_c["Ytr"].dtype == jnp.int32
    spec_s = surf_batch_specs(SCFG)
    assert spec_s["Xtr"].shape[-1] == SCFG.task.signal_dim
    assert spec_s["Ytr"].dtype == jnp.float32


def test_async_eval_runs_task_aware(sparse_mds):
    state, _, S = surf.train_surf(SCFG, sparse_mds, steps=4, log_every=0)
    out = surf.evaluate_async(SCFG, state, S, sparse_mds, n_async=2,
                              task=sparse_recovery_task(SCFG))
    assert out["acc_per_layer"].shape == (SCFG.n_layers,)
    assert np.isfinite(out["final_loss"])


# -------------------------------------------- multi-device (sharded lane)
@multi_device
def test_sparse_recovery_through_halo_mixer(sparse_mds):
    """Tentpole acceptance (sharded lane): the sparse task trains through
    the halo ppermute exchange with no task-specific branch in engine/ —
    matching the dense path to fp32 tolerance."""
    from repro.launch.mesh import make_agent_mesh
    mesh = make_agent_mesh(8)
    st_d, _, S = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0)
    st_h, _, _ = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0,
                                 mix="halo", mesh=mesh)
    for x, y in zip(jax.tree_util.tree_leaves(st_d),
                    jax.tree_util.tree_leaves(st_h)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


@multi_device
def test_sparse_recovery_through_ring_mixer(sparse_mds):
    from repro.launch.mesh import make_agent_mesh
    cfg = dataclasses.replace(SCFG, topology="ring", degree=2)
    mesh = make_agent_mesh(8)
    st_d, _, _ = surf.train_surf(cfg, sparse_mds, steps=6, log_every=0)
    st_r, _, _ = surf.train_surf(cfg, sparse_mds, steps=6, log_every=0,
                                 mix="ring", mesh=mesh)
    for x, y in zip(jax.tree_util.tree_leaves(st_d),
                    jax.tree_util.tree_leaves(st_r)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


@multi_device
def test_sparse_recovery_through_scheduled_halo(sparse_mds):
    from repro.launch.mesh import make_agent_mesh
    from repro.topology import families as F
    from repro.topology import schedule as SCH
    from repro.topology.halo import make_scheduled_halo_mix
    mesh = make_agent_mesh(8)
    A = F.regular_graph(SCFG.n_agents, 3, seed=0)
    sch = SCH.link_failure_schedule(A, 6, p_fail=0.2, seed=3)
    st_d, _, _ = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0,
                                 schedule=sch)
    mix_fn = make_scheduled_halo_mix(mesh, "data", sch)
    st_h, _, _ = surf.train_surf(SCFG, sparse_mds, steps=6, log_every=0,
                                 schedule=sch, mix_fn=mix_fn, mesh=mesh)
    for x, y in zip(jax.tree_util.tree_leaves(st_d),
                    jax.tree_util.tree_leaves(st_h)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)
