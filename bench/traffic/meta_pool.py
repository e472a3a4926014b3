"""Meta-training traffic: a pool of ``pool`` federations made on the device
from the seed and cycled round-robin by the program's scan, driven in
chunks of ``steps_per_chunk`` meta-steps between host waits."""
from __future__ import annotations

import surfgen


def pool_size(params, cfg):
    return int(params.get("pool", cfg["meta_pool"]))


def make(key, cfg, params):
    """The stacked pool {Xtr (Q, n, m, F), Ytr, Xte, Yte} on the device."""
    return surfgen.make_pool(key, cfg, pool_size(params, cfg))
