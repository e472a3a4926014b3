"""Open-loop Poisson traffic of new federations at a fixed ``rate``.

Every seed gets the same set of inter-arrival gaps, the exponential
distribution's quantiles at (i + 0.5) / N for N = rate · seconds, scaled
so that the last request is due at ``seconds``; the seed only shuffles
their order. The set of federations (``federations`` of them, each a
fresh graph and dataset) is made from the seed; request i solves
federation i mod ``federations`` with its own solve seed i, so no two
requests are the same solve.
"""
from __future__ import annotations

import numpy as np

import surfgen


def due_times(rate, seconds, seed):
    """Seconds from the window's start at which each request is due."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([int(seed), 3]).permutation(gaps)
    due = np.cumsum(gaps)
    return due * (seconds / due[-1])


def federations(key, cfg, count, seed, piece=8):
    """``count`` federations on the host: (S, dataset) pairs. They are made
    on the device ``piece`` at a time, so that set-up never holds more of
    them there than a tick does."""
    import jax
    build = surfgen.pool_maker(cfg, piece)
    out = []
    for start in range(0, count, piece):
        part = jax.device_get(build(key, start))
        for j in range(min(piece, count - start)):
            S = surfgen.mixing_matrix(cfg, graph_seed(seed, start + j))
            out.append((S, {k: v[j] for k, v in part.items()}))
    return out


def graph_seed(seed, i):
    return [int(seed), 1000 + i]
