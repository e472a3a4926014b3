"""U-DGD: DGD unrolled into GNN layers (paper §5, eq. U-DGD).

One unrolled layer at agent i:
    w_{i,l} = [H_l(W_{l-1})]_i  −  σ( M_l [w_{i,l-1} ∥ b_{i,l}] + d_l )
where H_l is a K-tap graph filter  H(W) = Σ_{k≤K} h_{k,l} S^k W  (K
communication rounds) and the perceptron (M_l, d_l) is shared by all
agents (⇒ permutation equivariance, Remark 5.1).

The L layers are a ``lax.scan`` over stacked per-layer parameters; each
layer consumes its own stochastic mini-batch (stochastic unrolling, §4).

The classical-FL (star) variant of §5.2 is obtained by (a) a star
topology S and (b) constraining K=1 — the server row of S aggregates,
agents update locally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import SURFConfig
from repro.core.tasks import resolve_task


def graph_filter(S, W, h):
    """Σ_k h_k S^k W, Horner form: K sparse-mixing rounds, not K matmul
    powers. h (K+1,), S (n,n), W (n,d)."""
    K = h.shape[0] - 1
    Y = h[K] * W
    for k in range(K - 1, -1, -1):
        Y = S @ Y + h[k] * W
    return Y


def _mix(mix_fn, S, W, h):
    """Apply the layer's graph filter through the mixer protocol:

      * ``mix_fn is None`` — the dense jnp Horner loop above;
      * ``mix_fn.takes_S`` — ``mix_fn(S, W, h)``: an S-as-ARGUMENT filter
        (``kernels.graph_filter.make_pallas_mix``) that fuses the K hops
        in one Pallas kernel; S stays a jit argument, so it composes
        with schedules (S_t) and the seed-batched vmap (per-lane S_i)
        exactly like the dense path;
      * otherwise — ``mix_fn(W, h)``: a baked-S collective exchange
        (ring / halo ``ppermute`` paths of ``core.ring`` /
        ``topology.halo``)."""
    with jax.named_scope("surf/mix"):
        if mix_fn is None:
            return graph_filter(S, W, h)
        if getattr(mix_fn, "takes_S", False):
            return mix_fn(S, W, h)
        return mix_fn(W, h)


def batch_vector(Xb, Yb, n_classes):
    """Legacy classification flattening (compat; layers now use
    ``task.batch_vector``): each example's features and one-hot label
    follow each other. Xb (n, b, F), Yb (n, b) -> (n, b*(F+C))."""
    oh = jax.nn.one_hot(Yb, n_classes, dtype=Xb.dtype)
    packed = jnp.concatenate([Xb, oh], axis=-1)          # (n, b, F+C)
    return packed.reshape(Xb.shape[0], -1)


def perceptron_in_dim(cfg: SURFConfig, task=None) -> int:
    task = resolve_task(cfg, task)
    return task.dim + cfg.batch_per_agent * task.batch_feat


def init_udgd(key, cfg: SURFConfig, dtype=jnp.float32, init="dgd", task=None):
    """Stacked per-layer parameters {h (L,K+1), M (L,din,d), d (L,d)}.

    init='dgd' starts h at the DGD point (pure one-hop mixing h=[0,1,0..],
    M near zero) — training starts at consensus dynamics. This is a
    beyond-paper stabilisation; init='random' is the generic init the
    paper's constraint-ablation story assumes (see fig7 benchmark).
    """
    task = resolve_task(cfg, task)
    L_, K = cfg.n_layers, cfg.filter_taps
    d = task.dim
    din = perceptron_in_dim(cfg, task)
    k1, k2 = jax.random.split(key)
    if init == "dgd":
        h0 = jnp.zeros((L_, K + 1)).at[:, min(1, K)].set(1.0)
        h = h0 + 0.01 * jax.random.normal(k1, (L_, K + 1))
        M = 0.01 * jax.random.normal(k2, (L_, din, d)) * (din ** -0.5)
    else:
        h = 0.5 * jax.random.normal(k1, (L_, K + 1))
        M = jax.random.normal(k2, (L_, din, d)) * (din ** -0.5)
    dd = jnp.zeros((L_, d))
    return {"h": h.astype(dtype), "M": M.astype(dtype), "d": dd.astype(dtype)}


def _perceptron(W, Xb, Yb, M, d, activation, task):
    """σ(M [w ∥ b] + d) per agent: the shared perceptron's local step."""
    with jax.named_scope("surf/perceptron"):
        b_in = task.batch_vector(Xb, Yb)
        z = jnp.concatenate([W, b_in], axis=-1) @ M + d      # (n, d)
        act = {"relu": jax.nn.relu, "tanh": jnp.tanh}[activation]
        return act(z)


def udgd_layer(params_l, S, W, Xb, Yb, cfg: SURFConfig, activation="relu",
               mix_fn=None, task=None, W_in=None):
    """One unrolled layer. W (n,d); Xb (n,b,F); Yb (n,b). ``mix_fn(W, h)``
    overrides the dense graph filter (e.g. the ring ppermute path); a
    ``takes_S`` mixer is called ``mix_fn(S, W, h)`` instead — the Pallas
    kernel path (see ``_mix``).

    ``W_in``: the perceptron's input where ``W`` and ``params_l``'s M and
    d hold only a block of the output columns (a θ split by columns, as
    the serving solver runs it): all of W's d columns, while the filter,
    which mixes rows, runs on the block alone."""
    task = resolve_task(cfg, task)
    h, M, d = params_l["h"], params_l["M"], params_l["d"]
    mixed = _mix(mix_fn, S, W, h)
    return mixed - _perceptron(W if W_in is None else W_in, Xb, Yb, M, d,
                               activation, task)


def udgd_forward(params, S, W0, Xl, Yl, cfg: SURFConfig, activation="relu",
                 mix_fn=None, task=None):
    """Run L layers. Xl (L,n,b,F), Yl (L,n,b).
    Returns (W_L, W_all (L+1,n,d) including W0). ``mix_fn`` overrides the
    dense graph filter in every layer (ring ppermute path)."""
    task = resolve_task(cfg, task)

    def body(W, xs):
        p_l, Xb, Yb = xs
        Wn = udgd_layer(p_l, S, W, Xb, Yb, cfg, activation, mix_fn=mix_fn,
                        task=task)
        return Wn, Wn
    W_L, Ws = jax.lax.scan(body, W0, (params, Xl, Yl))
    W_all = jnp.concatenate([W0[None], Ws], axis=0)
    return W_L, W_all


def probe_batch(batch, cfg: SURFConfig):
    """The held-aside convergence-probe batch: the first
    ``cfg.probe_size`` TRAINING rows per agent (capped at the split
    size). Drawn without touching the RNG stream — the pre-sampled
    per-layer mini-batch stack stays bit-identical to the fixed-depth
    path — and small, so the early-exit certificate is cheap relative
    to a full layer."""
    p = min(int(cfg.probe_size), int(batch["Xtr"].shape[1]))
    return batch["Xtr"][:, :p], batch["Ytr"][:, :p]


def udgd_forward_adaptive(params, S, W0, Xl, Yl, Xp, Yp, cfg: SURFConfig,
                          activation="relu", mix_fn=None, task=None,
                          layer_fn=None):
    """Convergence-adaptive forward: run unrolled layers under
    ``lax.while_loop`` (fixed-L trip bound — compilation stays bounded)
    with layer parameters and mini-batches selected by
    ``lax.dynamic_index_in_dim``, exiting once the probe-batch grad-norm
    ratio ‖∇f(W_l)‖/‖∇f(W_{l-1})‖ reaches 1 − ``cfg.exit_threshold``
    (the layer bought less than an ``exit_threshold`` fractional
    descent — the descending-constraint certificate of
    ``core.constraints``, repurposed as a STOPPING rule) and at least
    ``cfg.min_layers`` layers have run.

    Xl/Yl are the SAME pre-sampled (L, n, b) stacks the fixed-depth
    ``udgd_forward`` consumes (``sample_layer_batches``), so the RNG
    stream is identical and ``exit_threshold == 0`` (early exit
    statically disabled) reproduces ``udgd_forward``'s W_L exactly.
    (Xp, Yp) is the held-aside probe split (``probe_batch``).

    Returns ``(W_L, depth)`` — the final iterate and the realized layer
    count (an int32 scalar, L when no certificate fired)."""
    task = resolve_task(cfg, task)
    if layer_fn is None:
        layer_fn = (udgd_layer_star if cfg.topology == "star"
                    else udgd_layer)
    L_ = cfg.n_layers
    thr = float(cfg.exit_threshold)
    min_l = int(cfg.min_layers)
    adaptive = thr > 0.0
    g0 = task.grad_norm(W0, Xp, Yp)

    def cond(carry):
        l, _, _, done = carry
        return (l < L_) & jnp.logical_not(done)

    def body(carry):
        l, W, g_prev, _ = carry
        p_l = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            params)
        Xb = jax.lax.dynamic_index_in_dim(Xl, l, 0, keepdims=False)
        Yb = jax.lax.dynamic_index_in_dim(Yl, l, 0, keepdims=False)
        Wn = layer_fn(p_l, S, W, Xb, Yb, cfg, activation, mix_fn=mix_fn,
                      task=task)
        g = task.grad_norm(Wn, Xp, Yp)
        if adaptive:
            ratio = g / jnp.maximum(g_prev, 1e-12)
            fire = (l + 1 >= min_l) & (ratio >= 1.0 - thr)
        else:
            fire = jnp.asarray(False)
        return (l + 1, Wn, g, fire)

    depth, W_L, _, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), W0, g0, jnp.asarray(False)))
    return W_L, depth


def star_filter_mask(cfg: SURFConfig):
    """§5.2: in classical FL the server (node 0) has no local data — its
    perceptron update is masked out; it only aggregates."""
    mask = jnp.ones((cfg.n_agents, 1))
    if cfg.topology == "star":
        mask = mask.at[0, 0].set(0.0)
    return mask


def udgd_layer_star(params_l, S, W, Xb, Yb, cfg: SURFConfig,
                    activation="relu", mix_fn=None, task=None):
    """Classical-FL layer: server node only aggregates (no local update).
    Same mixer protocol as ``udgd_layer`` (see ``_mix``)."""
    task = resolve_task(cfg, task)
    h, M, d = params_l["h"], params_l["M"], params_l["d"]
    mixed = _mix(mix_fn, S, W, h)
    return mixed - star_filter_mask(cfg) * _perceptron(W, Xb, Yb, M, d,
                                                       activation, task)


def sample_w0(key, cfg: SURFConfig, task=None):
    with jax.named_scope("surf/featurize"):
        return resolve_task(cfg, task).init_state(key, cfg)


def featurize_cohort(key, batch, cfg: SURFConfig, task=None):
    """The stochastic featurization ONE solve of a cohort consumes: split
    the solve key into (W0, minibatch) streams, draw W0 ~ N(μ0, σ0²I)
    and the L per-layer per-agent mini-batches from the cohort's
    training split. Returns (W0 (n,d), Xl (L,n,b,F), Yl (L,n,b)).

    This is the exact stream ``engine.core._eval_core`` /
    ``core.surf._async_core`` consume per dataset, factored out so the
    serving layer (``repro.serve``) can featurize a request at its TRUE
    cohort shape at admission time and stay bit-identical to the
    ``evaluate_surf`` solve of the same (cfg, key) — shape buckets pad
    AFTER this step, so padding never perturbs the RNG stream."""
    kw, kb = jax.random.split(key)
    W0 = sample_w0(kw, cfg, task=task)
    Xl, Yl = sample_layer_batches(kb, batch["Xtr"], batch["Ytr"], cfg)
    return W0, Xl, Yl


def sample_layer_batches(key, Xtr, Ytr, cfg: SURFConfig):
    """Stochastic unrolling: one independent uniform mini-batch per layer per
    agent. Xtr (n, m, F), Ytr (n, m) -> (L, n, b, F), (L, n, b).

    The rows are picked by a one-hot contraction, not a gather. On a TPU
    v5e (JAX 0.9.0, libtpu 0.0.34), at the paper's widths (L=10, n=100,
    m=45, F=512), programs holding the gather sometimes never finished;
    with the contraction none has stalled so far, but the cause in
    libtpu was not isolated. Each output is one row plus exact zeros,
    and HIGHEST precision keeps every float32 bit, so the rows are the
    gather's (``chip_smoke.py`` checks this on the chip). One difference:
    0·Inf and 0·NaN are NaN, so a non-finite entry anywhere in an
    agent's ``Xtr`` makes every row sampled for that agent NaN, where
    the gather spoiled only the batches that drew that row."""
    L_, n, b = cfg.n_layers, cfg.n_agents, cfg.batch_per_agent
    m = Xtr.shape[1]
    with jax.named_scope("surf/featurize"):
        idx = jax.random.randint(key, (L_, n, b), 0, m)
        pick = idx[..., None] == jnp.arange(m)                # (L, n, b, m)
        Xl = jnp.einsum("lnbm,nmf->lnbf", pick.astype(Xtr.dtype), Xtr,
                        precision=jax.lax.Precision.HIGHEST)
        Yl = jnp.sum(jnp.where(pick, Ytr[None, :, None, :], 0), axis=-1,
                     dtype=Ytr.dtype)
    return Xl, Yl
