"""Hypothesis property-based tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import MoEConfig, SURFConfig
from repro.core import constraints as C
from repro.core import unroll as U
from repro.models import layers as L
from repro.models import moe as MO
from repro.topology import families as G

SET = dict(max_examples=15, deadline=None)


# ------------------------------------------------- U-DGD permutation equiv.
@settings(**SET)
@given(st.integers(0, 10_000))
def test_udgd_permutation_equivariance(seed):
    """Remark 5.1: relabeling agents permutes U-DGD outputs accordingly:
    φ(PW, PSPᵀ, PB) = P φ(W, S, B)."""
    rng = np.random.default_rng(seed)
    cfg = SURFConfig(n_agents=6, n_layers=1, filter_taps=2, feature_dim=4,
                     n_classes=3, batch_per_agent=2)
    key = jax.random.PRNGKey(seed % 997)
    theta = U.init_udgd(key, cfg)
    theta_l = jax.tree_util.tree_map(lambda a: a[0], theta)
    _, Smat = G.build_topology("regular", cfg.n_agents, degree=3,
                               seed=seed % 13)
    S = jnp.asarray(Smat, jnp.float32)
    W = jnp.asarray(rng.normal(size=(6, cfg.head_dim)), jnp.float32)
    Xb = jnp.asarray(rng.normal(size=(6, 2, 4)), jnp.float32)
    Yb = jnp.asarray(rng.integers(0, 3, size=(6, 2)), jnp.int32)
    perm = rng.permutation(6)
    out = U.udgd_layer(theta_l, S, W, Xb, Yb, cfg)
    out_p = U.udgd_layer(theta_l, S[perm][:, perm], W[perm], Xb[perm],
                         Yb[perm], cfg)
    np.testing.assert_allclose(out[perm], out_p, atol=1e-4)


@settings(**SET)
@given(st.integers(0, 10_000), st.floats(0.1, 5.0))
def test_graph_filter_linearity(seed, scale):
    rng = np.random.default_rng(seed)
    S = jnp.asarray(rng.random((8, 8)), jnp.float32)
    W1 = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    W2 = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(3,)), jnp.float32)
    lhs = U.graph_filter(S, W1 + scale * W2, h)
    rhs = U.graph_filter(S, W1, h) + scale * U.graph_filter(S, W2, h)
    np.testing.assert_allclose(lhs, rhs, atol=1e-3)


@settings(**SET)
@given(st.integers(4, 24), st.integers(0, 1000))
def test_metropolis_doubly_stochastic(n, seed):
    deg = min(3, n - 1)
    if n * deg % 2:
        deg -= 1
    if deg < 1:
        return
    A, W = G.build_topology("regular", n, degree=deg, seed=seed)
    np.testing.assert_allclose(W.sum(0), 1.0, atol=1e-9)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-9)
    assert (W >= 0).all()


# ----------------------------------------------------------- constraints
@settings(**SET)
@given(st.lists(st.floats(1e-3, 10.0), min_size=2, max_size=8),
       st.floats(0.01, 0.5))
def test_slack_sign_iff_descending(gnorms, eps):
    g = jnp.asarray(gnorms)
    s = np.asarray(C.slacks(g, eps))
    for l in range(1, len(gnorms)):
        desc = gnorms[l] <= (1 - eps) * gnorms[l - 1]
        assert (s[l - 1] <= 1e-6) == desc


@settings(**SET)
@given(st.lists(st.floats(-2, 2), min_size=3, max_size=6),
       st.lists(st.floats(0, 3), min_size=3, max_size=6),
       st.floats(0.01, 1.0))
def test_dual_ascent_nonnegative(slack, lam, lr):
    n = min(len(slack), len(lam))
    out = C.dual_ascent(jnp.asarray(lam[:n]), jnp.asarray(slack[:n]), lr)
    assert bool(jnp.all(out >= 0))


# ----------------------------------------------------------------- models
@settings(**SET)
@given(st.integers(1, 4), st.integers(1, 64), st.integers(0, 100))
def test_rope_norm_preserved(b, s, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    cos, sin = L.rope_angles(pos, 16, 1e4)
    y = L.apply_rope(x, cos, sin)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-4)


@settings(**SET)
@given(st.integers(2, 32), st.integers(1, 4), st.integers(0, 500))
def test_moe_route_weights_normalized(T, k, seed):
    E = 8
    m = MoEConfig(n_experts=E, top_k=k)
    p = MO.init_moe(jax.random.PRNGKey(seed), 8, m, 16, "swiglu",
                    jnp.float32)
    x2 = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, 8))
    w, idx, lb, z = MO.route(p, x2, m)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert bool(jnp.all(idx >= 0)) and bool(jnp.all(idx < E))
    # top-k indices are distinct per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == k


@settings(**SET)
@given(st.integers(2, 40), st.integers(0, 300))
def test_moe_dispatch_capacity_never_exceeded(T, seed):
    m = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.0)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (T, 2), 0, 4)
    tok_idx, _ = MO.dispatch_indices(idx, T, m)
    C_ = MO.capacity(T, m)
    assert tok_idx.shape == (4, C_)
    ti = np.asarray(tok_idx)
    assert ((ti == T) | (ti < T)).all()


@settings(**SET)
@given(st.integers(0, 400))
def test_cross_entropy_bounds(seed):
    from repro.models.model import cross_entropy
    V = 17
    logits = jax.random.normal(jax.random.PRNGKey(seed), (2, 5, V))
    labels = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 5), 0, V)
    ce = float(cross_entropy(logits, labels))
    assert ce > 0
    # uniform logits => exactly log V
    ce_u = float(cross_entropy(jnp.zeros((1, 3, V)), labels[:1, :3]))
    np.testing.assert_allclose(ce_u, np.log(V), rtol=1e-5)


# --------------------------------------------------------------- sharding
@settings(**SET)
@given(st.integers(1, 4096), st.integers(1, 4096))
def test_param_spec_divisibility(d1, d2):
    """Whatever the dims, the chosen spec only shards divisible axes."""
    from repro.sharding.rules import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = param_spec("w", (d1, d2), FakeMesh())
    for dim, s in zip((d1, d2), tuple(spec)):
        if s == "model":
            assert dim % 16 == 0
        if s == ("data",):
            assert dim % 16 == 0
