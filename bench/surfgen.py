"""The benchmark's own inputs, made from the seed: graphs and their
Metropolis weights, synthetic frozen-feature federations and the weights
θ of the unrolled network.

Nothing here imports the program: the reference (``reference.py``) makes
the same inputs again from the same seed, and the program is handed them.

Federations follow ``data/synthetic.sample_dataset``'s distribution: fixed
class means (one fixed key, shared by every seed), a Dirichlet label mix
per federation shared by its agents, and Gaussian feature noise. They are
made on the device, one federation per step of a ``lax.map``, so that a
pool of hundreds is built in one call without a second copy of it.
"""
from __future__ import annotations

import numpy as np

MEANS_KEY = 1234           # the frozen backbone: the same for every seed


def dims(cfg):
    """(d, din): the per-agent weight size F·C + C of the softmax head, and
    the perceptron's input size d + b·(F + C)."""
    F, C, b = cfg["feature_dim"], cfg["n_classes"], cfg["batch_per_agent"]
    d = F * C + C
    return d, d + b * (F + C)


# ------------------------------------------------------------------ graphs
def regular_graph(n, degree, rng, tries=100):
    """Edges (u, v), u < v, of a connected random ``degree``-regular graph
    on ``n`` nodes: the union of ``degree // 2`` random Hamiltonian cycles
    and, for an odd degree, a random perfect matching, redrawn until no
    edge repeats. The first cycle makes it connected. Vectorised: each
    try is O(n·degree)."""
    if degree < 2 or degree >= n or (n * degree) % 2:
        raise ValueError(f"no connected {degree}-regular graph on {n} "
                         "nodes from cycles and a matching")
    for _ in range(tries):
        parts = []
        for _ in range(degree // 2):
            p = rng.permutation(n)
            parts.append(np.stack([p, np.roll(p, -1)], 1))
        if degree % 2:
            p = rng.permutation(n)
            parts.append(p.reshape(-1, 2))
        e = np.sort(np.concatenate(parts), axis=1)
        code = e[:, 0].astype(np.int64) * n + e[:, 1]
        if np.unique(code).size == code.size:
            return e[:, 0], e[:, 1]
    raise RuntimeError(f"no simple {degree}-regular graph on {n} nodes "
                       f"after {tries} tries")


def metropolis(n, u, v):
    """Dense symmetric doubly-stochastic Metropolis matrix (float32):
    1/(1 + max(deg_i, deg_j)) on each edge, the rest of each row on the
    diagonal."""
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    w = 1.0 / (1.0 + np.maximum(deg[u], deg[v]))
    S = np.zeros((n, n), np.float64)
    S[u, v] = w
    S[v, u] = w
    S[np.arange(n), np.arange(n)] = 1.0 - S.sum(1)
    return S.astype(np.float32)


def mixing_matrix(cfg, seed):
    """The cell's graph for ``seed`` (a whole number or a list of them):
    S (n, n) float32 on the host."""
    rng = np.random.default_rng([int(s) for s in np.atleast_1d(seed)] + [7])
    u, v = regular_graph(cfg["n_agents"], cfg["degree"], rng)
    return metropolis(cfg["n_agents"], u, v)


# ------------------------------------------------------------- federations
def class_means(cfg):
    import jax
    import jax.numpy as jnp
    mu = jax.random.normal(jax.random.PRNGKey(MEANS_KEY),
                           (cfg["n_classes"], cfg["feature_dim"]))
    return cfg["class_sep"] * mu / jnp.linalg.norm(mu, axis=1,
                                                   keepdims=True)


def federation(key, cfg, mu):
    """One federation {Xtr (n, m, F), Ytr (n, m), Xte (n, t, F), Yte}.
    Features are picked from the class means by a one-hot contraction
    (exact at HIGHEST precision), not by a gather."""
    import jax
    import jax.numpy as jnp
    n, C = cfg["n_agents"], cfg["n_classes"]
    kp, ktr, kte = jax.random.split(key, 3)
    probs = jax.random.dirichlet(kp, cfg["label_dirichlet"] * jnp.ones(C))
    logp = jnp.log(probs)

    def split(k, rows):
        ky, kx = jax.random.split(k)
        y = jax.random.categorical(ky, logp, shape=(n, rows))
        means = jnp.einsum("nrc,cf->nrf", jax.nn.one_hot(y, C), mu,
                           precision=jax.lax.Precision.HIGHEST)
        x = means + cfg["feature_noise"] * jax.random.normal(
            kx, means.shape)
        return x.astype(jnp.float32), y.astype(jnp.int32)
    Xtr, Ytr = split(ktr, cfg["train_per_agent"])
    Xte, Yte = split(kte, cfg["test_per_agent"])
    return {"Xtr": Xtr, "Ytr": Ytr, "Xte": Xte, "Yte": Yte}


def pool_key(key):
    import jax
    return jax.random.fold_in(key, 11)


def pool_maker(cfg, Q):
    """Jitted ``(key, start)`` → federations ``start .. start + Q − 1`` of
    the pool of ``key``, stacked on a leading axis; federation q is
    ``federation(fold_in(pool_key(key), q))``. One program for every
    ``start``, so a large set is made in pieces of Q."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(k, start):
        mu = class_means(cfg)
        return jax.lax.map(
            lambda q: federation(jax.random.fold_in(k, q), cfg, mu),
            start + jnp.arange(Q))
    return lambda key, start=0: build(pool_key(key), start)


def make_pool(key, cfg, Q):
    """Q federations stacked on a leading axis, in one jitted call."""
    return pool_maker(cfg, Q)(key)


def pool_member(key, cfg, q):
    """Federation q of ``make_pool(key, cfg, Q)``, alone."""
    import jax
    return jax.jit(lambda k: federation(
        jax.random.fold_in(pool_key(k), q), cfg, class_means(cfg)))(key)


# ------------------------------------------------------------------ weights
def theta_key(key):
    import jax
    return jax.random.fold_in(key, 13)


def theta_layer(key, cfg, layer, scale):
    """Layer ``layer`` of θ: taps at the one-hop (DGD) point plus 0.01
    noise, M ~ scale · N(0, 1/din), d = 0."""
    import jax
    import jax.numpy as jnp
    d, din = dims(cfg)
    K = cfg["filter_taps"]
    kh, km = jax.random.split(jax.random.fold_in(key, layer))
    h = jnp.zeros(K + 1).at[min(1, K)].set(1.0) + 0.01 * jax.random.normal(
        kh, (K + 1,))
    M = (scale * din ** -0.5) * jax.random.normal(km, (din, d))
    return {"h": h, "M": M, "d": jnp.zeros(d)}


def make_theta(key, cfg, scale):
    """θ = {h (L, K+1), M (L, din, d), d (L, d)} in float32, in one jitted
    call on the device."""
    import jax
    import jax.numpy as jnp
    L = cfg["n_layers"]
    return jax.jit(lambda k: jax.lax.map(
        lambda l: theta_layer(k, cfg, l, scale), jnp.arange(L)))(
            theta_key(key))


def change_norms(theta, key, cfg):
    """Per-leaf ‖θ − θ0‖, with θ0 made again layer by layer from the
    seed, so that θ0 is never whole on the device beside θ."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(th, k):
        def one(l):
            p0 = theta_layer(k, cfg, l, cfg["theta_scale"])
            return {n: jnp.sum(jnp.square(th[n][l].astype(jnp.float32)
                                          - p0[n])) for n in p0}
        sq = jax.lax.map(one, jnp.arange(cfg["n_layers"]))
        return {n: jnp.sqrt(jnp.sum(v)) for n, v in sq.items()}
    return norms(theta, theta_key(key))
