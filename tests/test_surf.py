"""Tests for the paper's core: graphs, U-DGD, constraints, Algorithm 1."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.surf_paper import SMOKE
from repro.core import constraints as C
from repro.core import surf
from repro import engine as TR
from repro.core import unroll as U
from repro.data import synthetic
from repro.topology import families as G

CFG = SMOKE


@pytest.fixture(scope="module")
def problem():
    A, S = surf.make_problem(CFG, seed=0)
    mds = synthetic.make_meta_dataset(CFG, 6, seed=0)
    return A, S, mds


# ----------------------------------------------------------------- graphs
@pytest.mark.parametrize("kind", ["regular", "er", "star", "ring"])
def test_topologies_connected_and_stochastic(kind):
    n = 12
    A, W = G.build_topology(kind, n, degree=3, p=0.4, seed=1)
    assert G.is_connected(A)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-9)   # row-stochastic
    np.testing.assert_allclose(W, W.T, atol=1e-12)          # symmetric
    assert (np.linalg.eigvalsh(W) <= 1 + 1e-9).all()


def test_consensus_via_mixing():
    """Repeated Metropolis mixing drives agents to the average (the
    mechanism behind the (FL) constraints)."""
    _, W = G.build_topology("regular", 10, degree=3, seed=2)
    x = np.random.default_rng(0).normal(size=(10, 4))
    y = x.copy()
    for _ in range(200):
        y = W @ y
    np.testing.assert_allclose(y, x.mean(0, keepdims=True).repeat(10, 0),
                               atol=1e-6)


# ----------------------------------------------------------------- U-DGD
def test_graph_filter_dgd_point(problem):
    """h=[0,1] reproduces one DGD mixing round S@W exactly."""
    _, S, _ = problem
    W = jnp.asarray(np.random.default_rng(1).normal(
        size=(CFG.n_agents, CFG.head_dim)), jnp.float32)
    Y = U.graph_filter(S, W, jnp.array([0.0, 1.0]))
    np.testing.assert_allclose(Y, S @ W, atol=1e-6)


def test_udgd_forward_shapes(problem, key):
    _, S, mds = problem
    theta = U.init_udgd(key, CFG)
    W0 = U.sample_w0(key, CFG)
    Xl, Yl = U.sample_layer_batches(key, jnp.asarray(mds[0]["Xtr"]),
                                    jnp.asarray(mds[0]["Ytr"]), CFG)
    W_L, W_all = U.udgd_forward(theta, S, W0, Xl, Yl, CFG)
    assert W_L.shape == (CFG.n_agents, CFG.head_dim)
    assert W_all.shape == (CFG.n_layers + 1, CFG.n_agents, CFG.head_dim)


def test_sample_layer_batches_picks_rows_exactly(problem, key):
    """The one-hot row pick returns bit-for-bit the rows a gather of the
    same uniform indices returns."""
    _, _, mds = problem
    Xtr, Ytr = mds[0]["Xtr"], mds[0]["Ytr"]
    Xl, Yl = U.sample_layer_batches(key, jnp.asarray(Xtr), jnp.asarray(Ytr),
                                    CFG)
    idx = np.asarray(jax.random.randint(
        key, (CFG.n_layers, CFG.n_agents, CFG.batch_per_agent), 0,
        Xtr.shape[1]))
    agents = np.arange(CFG.n_agents)[None, :, None]
    np.testing.assert_array_equal(np.asarray(Xl), Xtr[agents, idx])
    np.testing.assert_array_equal(np.asarray(Yl), Ytr[agents, idx])
    assert Yl.dtype == Ytr.dtype


def test_star_server_row_only_aggregates(key):
    import dataclasses
    cfg = dataclasses.replace(CFG, topology="star", filter_taps=1)
    _, S = surf.make_problem(cfg, seed=0)
    theta_l = {"h": jnp.array([0.0, 1.0]),
               "M": jnp.ones((U.perceptron_in_dim(cfg), cfg.head_dim)),
               "d": jnp.zeros((cfg.head_dim,))}
    W = jnp.ones((cfg.n_agents, cfg.head_dim))
    Xb = jnp.ones((cfg.n_agents, cfg.batch_per_agent, cfg.feature_dim))
    Yb = jnp.zeros((cfg.n_agents, cfg.batch_per_agent), jnp.int32)
    Wn = U.udgd_layer_star(theta_l, S, W, Xb, Yb, cfg)
    mixed = U.graph_filter(S, W, theta_l["h"])
    np.testing.assert_allclose(Wn[0], mixed[0], atol=1e-6)  # server: no update
    assert not np.allclose(Wn[1], mixed[1])                  # agents: update


# ------------------------------------------------------------ constraints
def test_slacks_definition():
    g = jnp.array([1.0, 0.9, 0.7, 0.8])
    s = C.slacks(g, eps=0.1)
    np.testing.assert_allclose(s, [0.9 - 0.9, 0.7 - 0.81, 0.8 - 0.63],
                               atol=1e-6)


def test_dual_ascent_projects():
    lam = jnp.array([0.5, 0.0])
    out = C.dual_ascent(lam, jnp.array([-10.0, 2.0]), lr=0.1)
    assert float(out[0]) == 0.0 and float(out[1]) == pytest.approx(0.2)


def test_grad_norm_second_order_differentiable(problem, key):
    """∇_θ‖∇_W f‖ — the grad-of-grad path the Lagrangian needs."""
    _, S, mds = problem
    theta = U.init_udgd(key, CFG)
    Xl, Yl = U.sample_layer_batches(key, jnp.asarray(mds[0]["Xtr"]),
                                    jnp.asarray(mds[0]["Ytr"]), CFG)
    W0 = U.sample_w0(key, CFG)
    def f(th):
        _, W_all = U.udgd_forward(th, S, W0, Xl, Yl, CFG)
        g = C.layer_grad_norms(W_all, Xl, Yl, CFG)
        return jnp.sum(g)
    grads = jax.grad(f)(theta)
    assert float(jnp.sum(jnp.abs(grads["h"]))) > 0


# -------------------------------------------------------------- training
def test_meta_training_learns(problem):
    _, S, mds = problem
    key = jax.random.PRNGKey(3)
    state = TR.init_state(key, CFG)
    meta_step, _ = TR.make_meta_step(CFG, S)
    accs = []
    for t in range(60):
        key, sub = jax.random.split(key)
        state, m = meta_step(state, mds[t % len(mds)], sub)
        accs.append(float(m["test_acc"]))
    assert np.mean(accs[-10:]) > np.mean(accs[:10]) + 0.2


@pytest.mark.slow
def test_constraints_make_trajectory_descend(problem):
    """Appendix D ablation: with constraints the per-layer loss decreases
    monotonically-ish; without, intermediate layers are unconstrained."""
    _, S, mds = problem
    key = jax.random.PRNGKey(4)
    out = {}
    for constrained in (True, False):
        state = TR.init_state(key, CFG)
        meta_step, _ = TR.make_meta_step(CFG, S, constrained=constrained)
        k = key
        for t in range(80):
            k, sub = jax.random.split(k)
            state, m = meta_step(state, mds[t % len(mds)], sub)
        ev = TR.make_eval(CFG, S)
        res = ev(state.theta, mds[0], jax.random.PRNGKey(9))
        out[constrained] = np.asarray(res["loss_per_layer"])
    # constrained trajectory: each layer ~descends (small tolerance)
    con = out[True]
    viol = np.sum(np.diff(con) > 0.05 * con[:-1] + 1e-3)
    assert viol <= 1, f"constrained trajectory not descending: {con}"


def test_evaluate_and_async(problem):
    _, S, mds = problem
    key = jax.random.PRNGKey(5)
    state = TR.init_state(key, CFG)
    res = surf.evaluate_surf(CFG, state, S, mds[:2])
    assert res["acc_per_layer"].shape == (CFG.n_layers,)
    res_a = surf.evaluate_async(CFG, state, S, mds[:2], n_async=2)
    assert 0.0 <= res_a["final_acc"] <= 1.0
