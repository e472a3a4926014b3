"""The benchmark's inputs: the same seed gives the same data, graphs are
regular, connected and weighted as stated, and arrivals are the same set
for every seed."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402
import surfgen  # noqa: E402

SMALL = {"n_agents": 6, "n_layers": 2, "filter_taps": 2, "feature_dim": 5,
         "n_classes": 3, "batch_per_agent": 2, "train_per_agent": 4,
         "test_per_agent": 3, "feature_noise": 1.0, "class_sep": 3.0,
         "label_dirichlet": 1.0, "degree": 3, "theta_scale": 0.1}


def connected(n, u, v):
    adj = [[] for _ in range(n)]
    for a, b in zip(u, v):
        adj[a].append(b)
        adj[b].append(a)
    seen, stack = {0}, [0]
    while stack:
        for b in adj[stack.pop()]:
            if b not in seen:
                seen.add(b)
                stack.append(b)
    return len(seen) == n


@pytest.mark.parametrize("n,degree", [(100, 3), (9343, 4), (64, 5)])
def test_regular_graph_is_regular_simple_connected(n, degree):
    u, v = surfgen.regular_graph(n, degree, np.random.default_rng(3))
    assert len(u) == n * degree // 2
    assert np.all(u < v)
    assert len(set(zip(u.tolist(), v.tolist()))) == len(u)
    assert np.all(np.bincount(np.concatenate([u, v]), minlength=n) == degree)
    assert connected(n, u, v)


def test_regular_graph_refuses_odd_stub_count():
    with pytest.raises(ValueError):
        surfgen.regular_graph(9343, 3, np.random.default_rng(0))


def test_metropolis_is_symmetric_doubly_stochastic():
    S = surfgen.mixing_matrix({"n_agents": 100, "degree": 3}, 2 ** 40 + 9)
    assert np.allclose(S, S.T)
    assert np.allclose(S.sum(0), 1.0) and np.allclose(S.sum(1), 1.0)
    off = S[~np.eye(100, dtype=bool)]
    assert set(np.unique(off).tolist()) == {0.0, 0.25}


def test_same_seed_same_inputs_other_seed_other_inputs():
    import jax
    k1, k2 = harness.seed_key(2 ** 33 + 5), harness.seed_key(2 ** 33 + 6)
    a, b = surfgen.make_pool(k1, SMALL, 3), surfgen.make_pool(k1, SMALL, 3)
    c = surfgen.make_pool(k2, SMALL, 3)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a["Xtr"]), np.asarray(c["Xtr"]))
    one = surfgen.pool_member(k1, SMALL, 2)
    for k in a:
        assert np.array_equal(np.asarray(a[k])[2], np.asarray(one[k]))
    t1 = surfgen.make_theta(k1, SMALL, 0.1)
    t2 = surfgen.make_theta(k1, SMALL, 0.1)
    assert all(np.array_equal(np.asarray(t1[k]), np.asarray(t2[k]))
               for k in t1)
    assert t1["M"].shape == (2, 18 + 2 * (5 + 3), 18)   # (L, din, d)
    # θ0 made again layer by layer matches to rounding (XLA may fuse the
    # scaling differently in the two programs)
    norms = surfgen.change_norms(t1, k1, SMALL)
    for k, v in norms.items():
        assert float(v) <= 1e-6 * float(np.linalg.norm(np.asarray(t1[k])))
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x: bool(np.all(np.isfinite(np.asarray(x)))), a))


def test_seed_key_holds_all_64_bits():
    assert np.asarray(harness.seed_key(2 ** 32 + 7)).tolist() == [1, 7]
    with pytest.raises(harness.BenchError):
        harness.seed_key(-1)


def test_poisson_arrivals_are_one_set_in_seeded_order():
    poisson = harness.load_module("traffic", "poisson")
    a = poisson.due_times(10.0, 30.0, 1)
    b = poisson.due_times(10.0, 30.0, 2 ** 40)
    assert len(a) == len(b) == 300
    assert a[-1] == pytest.approx(30.0) and b[-1] == pytest.approx(30.0)
    assert np.all(np.diff(a) > 0)
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(b, prepend=0.0)))
    assert not np.allclose(a, b)
    assert np.array_equal(a, poisson.due_times(10.0, 30.0, 1))
