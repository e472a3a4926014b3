"""The plain serving reference computed by layer blocks, for a θ that one
chip cannot hold whole.

It is ``reference.make_solve``'s math on the same inputs, in another
order: θ's layer l is made alone from the seed
(``surfgen.theta_layer``), every compared request is advanced through
it, and only then is layer l + 1 made, so that one chip holds one layer
of θ (4.78 GB at 62 classes) and the requests' W, never all of θ. The
requests are those of ``reference.serve_reference``: W0 and the layer
rows drawn again from each request's solve seed (``reference.draws``,
``reference.gather_rows``), the layer ``reference.layer`` at HIGHEST
precision in float32, or every array in bfloat16 for the control.
"""
from __future__ import annotations

import numpy as np

import reference
import surfgen


def solve_requests(cfg, key, requests, dtype):
    """Reference answers (W_L, final loss, final accuracy) for
    ``requests``, a list of (S, federation, solve seed), with θ made
    layer by layer from ``key`` at ``cfg["theta_scale"]``."""
    import jax
    import jax.numpy as jnp
    F, C, L = cfg["feature_dim"], cfg["n_classes"], cfg["n_layers"]
    prec = reference._prec(dtype)
    make_layer = jax.jit(lambda k, l: reference._cast(
        surfgen.theta_layer(k, cfg, l, cfg["theta_scale"]), dtype))
    step = jax.jit(jax.vmap(
        lambda p, S, W, Xb, Yb: reference.layer(p, S, W, Xb, Yb, F, C, prec),
        in_axes=(None, 0, 0, 0, 0)))

    @jax.jit
    @jax.vmap
    def scores(W, Xte, Yte):
        loss = jnp.mean(reference.head_grad(W, Xte, Yte, F, C, prec)[0])
        return loss, reference.accuracy(W, Xte, Yte, F, C, prec)

    draws = [reference.draws(reference.solve_key(seed), cfg,
                             np.asarray(fed["Xtr"]).shape[1])
             for _, fed, seed in requests]
    rows = [reference.gather_rows(fed, idx)
            for (_, fed, _), (_, idx) in zip(requests, draws)]
    S, W = reference._cast((jnp.stack([jnp.asarray(S) for S, _, _ in
                                       requests]),
                            jnp.stack([W0 for W0, _ in draws])), dtype)
    k = surfgen.theta_key(key)
    for l in range(L):
        p = make_layer(k, l)
        Xb = reference._cast(jnp.asarray(np.stack([X[l] for X, _ in rows])),
                             dtype)
        Yb = jnp.asarray(np.stack([Y[l] for _, Y in rows]))
        W = step(p, S, W, Xb, Yb)
        del p
    Xte = reference._cast(jnp.asarray(np.stack(
        [np.asarray(fed["Xte"]) for _, fed, _ in requests])), dtype)
    Yte = jnp.asarray(np.stack([np.asarray(fed["Yte"])
                                for _, fed, _ in requests]))
    loss, acc = scores(W, Xte, Yte)
    W = np.asarray(W, np.float32)
    return [(W[i], float(loss[i]), float(acc[i]))
            for i in range(len(requests))]
