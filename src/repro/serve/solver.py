"""Request-batched amortized solver: the serving hot path.

SURF's headline property is amortization — after meta-training, ONE
forward pass of the unrolled network solves a brand-new federation
(paper §4).  Serving turns that into a batched primitive: a REQUEST
BATCH of cohorts, stacked to a common bucket shape ``(B, n_pad, ...)``
with per-request mixing matrices, runs through one jitted
``vmap``-over-requests forward.  Three invariants make it correct and
fast:

  * S-as-argument — exactly like the engine/eval paths, every request's
    S rides through jit as data, so one executable serves every
    topology of a bucket shape;
  * masked padding — padded AGENT rows are zeroed through every layer
    (zero S rows/cols make them invisible to the graph filter) and
    padded TEST rows are row-0 copies un-biased by the task's
    ``padded_local_*`` corrections, so a padded solve returns the
    unpadded cohort's numbers;
  * admission-time featurization — ``core.unroll.featurize_cohort`` ran
    at the request's TRUE shape before padding (jax RNG draws are
    shape-dependent), so an exact-fit request reproduces
    ``evaluate_surf`` bit-for-bit.

On a mesh with a 'theta' axis (``launch.mesh.serve_mesh``) θ's
perceptron is split by output columns: each device holds its block of
M's and d's columns and of W's, with the columns padded with exact zeros
to a multiple of the split. The graph filter mixes rows, so it runs on
the block alone; the perceptron needs all of W, which one all-gather a
layer over the 'theta' axis (named scope ``surf/gather``) brings: W0 is
gathered before the first layer and each layer's output at its end, where
the layer's loss and accuracy read it too.

The per-bucket executable cache key extends ``engine._engine_cache_key``
with the bucket dims; ``engine.TRACE_COUNTS["serve"]`` counts body
traces (the bench asserts one per warm bucket, zero at request rate).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import engine as TR
from repro.configs.base import SURFConfig
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.sharding.surf_rules import (_axis_size, axis_for_role,
                                       check_divides, padded_columns,
                                       replicated, theta_shardings,
                                       theta_split)

SERVE_MIXES = (None, "dense", "pallas")


def resolve_serve_mix(mix):
    """Serving supports the S-as-argument mixers only: None/"dense" (the
    jnp Horner filter) or "pallas" (the fused kernel).  Baked-S mixers
    (ring/halo) close over ONE topology and cannot serve per-request
    graphs."""
    if mix in (None, "dense"):
        return None
    if mix == "pallas":
        from repro.kernels.graph_filter import make_pallas_mix
        return make_pallas_mix()
    raise ValueError(
        f"serve mix must be one of {SERVE_MIXES}, got {mix!r} — baked-S "
        "mixers (ring/halo) cannot serve per-request topologies")


def _masked_scores(task):
    """Padded-cohort loss/metric: the task's ``padded_local_*``
    row-corrections per agent, averaged over REAL agents only."""
    def masked_scores(W, Xte, Yte, mask, t_real):
        with jax.named_scope("surf/loss"):
            per_loss = jax.vmap(task.padded_local_loss,
                                in_axes=(0, 0, 0, None))(W, Xte, Yte, t_real)
            per_met = jax.vmap(task.padded_local_metric,
                               in_axes=(0, 0, 0, None))(W, Xte, Yte, t_real)
            denom = jnp.maximum(jnp.sum(mask), 1.0)
            loss = jnp.sum(jnp.where(mask, per_loss, 0.0)) / denom
            met = jnp.sum(jnp.where(mask, per_met, 0.0)) / denom
            return loss, met

    return masked_scores


def _serve_core(cfg: SURFConfig, activation, mix_fn=None, task=None,
                theta_axis=None):
    """Single-cohort masked forward ``solve_s(S, theta, W0, Xl, Yl, Xte,
    Yte, mask, t_real)`` at a bucket shape.  ``mask`` (n_pad,) flags real
    agents; ``t_real`` is the request's true test-rows count (its padded
    rows are row-0 copies — see ``buckets.pad_cohort``).

    ``theta_axis``: the mesh axis θ's columns are split over (inside a
    ``shard_map``); W0, M, d and the returned W are then this device's
    column blocks, and each layer all-gathers W once (module doc)."""
    task = resolve_task(cfg, task)
    masked_scores = _masked_scores(task)
    d = task.dim

    def whole(W):
        """All of W's real columns, from every device's block."""
        with jax.named_scope("surf/gather"):
            return jax.lax.all_gather(W, theta_axis, axis=1,
                                      tiled=True)[:, :d]

    def solve_s(S, theta, W0, Xl, Yl, Xte, Yte, mask, t_real):
        TR.TRACE_COUNTS["serve"] += 1

        def body(carry, xs):
            W, W_in = carry     # W_in: all of W's columns (split θ only)
            p_l, Xb, Yb = xs[:3]
            if theta_axis is not None:
                # a loop-variant 1.0 on the layer's block of M: without it
                # XLA hoists the matmul's bfloat16 cast out of the loop
                # and casts θ's whole block at once, half again its
                # bytes, which a device holding a split θ has no room
                # for (an optimization_barrier on the block does not stop
                # it: the cast moves through the barrier)
                p_l = dict(p_l, M=p_l["M"] * (1.0 + 0.0 * xs[3]))
            Wn = U.udgd_layer(p_l, S, W, Xb, Yb, cfg, activation,
                              mix_fn=mix_fn, task=task, W_in=W_in)
            # re-zero padded agents: their perceptron term σ(M[0∥b]+d)
            # is nonzero even on zero inputs (the bias d), and zero S
            # rows only silence them in the NEXT layer's filter
            Wn = jnp.where(mask[:, None], Wn, 0.0)
            Wn_in = None if theta_axis is None else whole(Wn)
            scored = Wn if Wn_in is None else Wn_in
            return (Wn, Wn_in), masked_scores(scored, Xte, Yte, mask, t_real)

        W0 = jnp.where(mask[:, None], W0, 0.0)
        xs, W0_in = (theta, Xl, Yl), None
        if theta_axis is not None:
            xs += (jnp.arange(cfg.n_layers, dtype=W0.dtype),)
            W0_in = whole(W0)
        (W_L, _), (losses, mets) = jax.lax.scan(body, (W0, W0_in), xs)
        return {"W": W_L, "loss_per_layer": losses, "acc_per_layer": mets,
                "final_loss": losses[-1], "final_acc": mets[-1]}

    return solve_s


def _serve_core_adaptive(cfg: SURFConfig, activation, mix_fn=None,
                         task=None):
    """Batched early-exit solver for one bucket: ``solve_batch(S, theta,
    W0, Xl, Yl, Xte, Yte, Xp, Yp, mask, t_real)`` with leading (B,)
    request axes on everything but theta.

    Unlike the fixed path (vmap-of-scan), the batch shares ONE
    ``lax.while_loop`` with a per-request ACTIVE mask: a request whose
    grad-norm certificate fires freezes its W (``jnp.where`` select) and
    stops accruing mixed/perceptron work logically; the loop exits when
    every request is done or L is reached, so the batch's realized trip
    count is max-over-requests depth.  The certificate uses
    ``task.masked_grad_norm`` on the padded probe split — zeroed padded
    rows and a real-agent denominator make it EQUAL to the unpadded
    ``grad_norm`` (adding 0.0 is exact), so padding can never flip an
    exit decision.  ``depth`` (B,) int32 is each request's realized
    layer count (0 for empty slots, whose all-zero mask starts them
    inactive)."""
    task = resolve_task(cfg, task)
    masked_scores = _masked_scores(task)
    L_ = cfg.n_layers
    thr = float(cfg.exit_threshold)
    min_l = int(cfg.min_layers)
    adaptive = thr > 0.0

    def solve_batch(S, theta, W0, Xl, Yl, Xte, Yte, Xp, Yp, mask, t_real):
        TR.TRACE_COUNTS["serve"] += 1
        TR.TRACE_COUNTS["adaptive"] += 1
        W0 = jnp.where(mask[:, :, None], W0, 0.0)
        act0 = jnp.any(mask, axis=1)                 # empty slots: done
        g0 = jax.vmap(task.masked_grad_norm)(W0, Xp, Yp, mask)
        dep0 = jnp.zeros_like(act0, jnp.int32)

        def layer(p_l, S1, W1, Xb1, Yb1):
            return U.udgd_layer(p_l, S1, W1, Xb1, Yb1, cfg, activation,
                                mix_fn=mix_fn, task=task)

        def cond(carry):
            l, _, _, act, _ = carry
            return (l < L_) & jnp.any(act)

        def body(carry):
            l, W, g_prev, act, dep = carry
            p_l = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, l, 0, keepdims=False), theta)
            Xb = jax.lax.dynamic_index_in_dim(Xl, l, 1, keepdims=False)
            Yb = jax.lax.dynamic_index_in_dim(Yl, l, 1, keepdims=False)
            Wn = jax.vmap(layer, in_axes=(None, 0, 0, 0, 0))(
                p_l, S, W, Xb, Yb)
            # same padded-agent re-zero as the fixed path, then freeze
            # requests whose certificate already fired
            Wn = jnp.where(mask[:, :, None], Wn, 0.0)
            Wn = jnp.where(act[:, None, None], Wn, W)
            g = jax.vmap(task.masked_grad_norm)(Wn, Xp, Yp, mask)
            g = jnp.where(act, g, g_prev)
            dep = dep + act.astype(jnp.int32)
            if adaptive:
                ratio = g / jnp.maximum(g_prev, 1e-12)
                fire = (l + 1 >= min_l) & (ratio >= 1.0 - thr)
                act = act & jnp.logical_not(fire)
            return (l + 1, Wn, g, act, dep)

        _, W_L, _, _, depth = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), W0, g0, act0, dep0))
        loss, met = jax.vmap(masked_scores)(W_L, Xte, Yte, mask, t_real)
        return {"W": W_L, "final_loss": loss, "final_acc": met,
                "depth": depth}

    return solve_batch


def serve_cache_key(cfg: SURFConfig, bucket, max_batch, activation,
                    mix_fn=None, task=None, depth="fixed", mesh=None):
    """Per-bucket executable key: ``engine._engine_cache_key`` with a
    ("serve", n_pad, t_pad, B) variant tag and the cohort-shape cfg
    fields scrubbed (the bucket dims subsume them — requests of any true
    size share the bucket's executable).  The adaptive path tags
    ("serve-adaptive", ..., thr, min_layers, probe_size) instead — the
    exit knobs are scrubbed from cfg by ``_engine_cache_key`` (fixed
    engines are shared across threshold sweeps) so they must ride in the
    variant here.  ``mesh`` rides through ``_engine_cache_key`` as its
    fingerprint — a request-sharded solver never collides with the
    single-device one.  None for an untagged custom mix_fn (uncacheable,
    same contract as the engine)."""
    variant = ("serve", int(bucket.n_agents), int(bucket.rows),
               int(max_batch))
    if depth == "adaptive":
        variant = ("serve-adaptive", int(bucket.n_agents),
                   int(bucket.rows), int(max_batch),
                   float(cfg.exit_threshold), int(cfg.min_layers),
                   int(cfg.probe_size))
    cfg = dataclasses.replace(cfg, n_agents=0, train_per_agent=0,
                              test_per_agent=0)
    return TR._engine_cache_key(cfg, variant, activation, False,
                                mesh=mesh, mix_fn=mix_fn, task=task)


def request_shardings(mesh, max_batch, depth="fixed"):
    """(in_shardings, out_shardings) for a bucket solver on ``mesh``: the
    REQUEST axis (leading B on every arg and output) shards over the
    mesh's agent-role axis; theta (arg 1) replicates, or, on a mesh with
    a 'theta' axis, splits M's and d's columns over it
    (``surf_rules.theta_shardings``), with W0's and the returned W's
    columns split alike.  Requests are embarrassingly parallel — the
    solver runs under a ``shard_map`` with these specs, so each device
    solves its block of request slots with no collective of its own (the
    adaptive path's ``any(active)`` loop predicate is per device); only a
    θ split adds its all-gathers of W.  ``max_batch`` must divide the
    shard count — ragged tails already ride as masked empty slots, so
    the constraint is on the BUCKET batch shape, not on traffic."""
    axis = axis_for_role(mesh, "agent")
    shards = _axis_size(mesh, axis)
    check_divides(max_batch, shards, "the sharded serve batch",
                  "max_batch",
                  "each device solves an equal block of request slots "
                  "(ragged traffic rides as masked empty slots)")
    req_axis = axis if shards > 1 else None
    req = NamedSharding(mesh, P(req_axis))
    cols = req
    if theta_split(mesh) > 1:
        if depth == "adaptive":
            raise ValueError(
                "adaptive-depth serving needs θ whole on every device: "
                "its exit certificate reads all of W's columns each "
                "layer; serve depth='fixed' on a mesh with a 'theta' axis")
        cols = NamedSharding(mesh, P(req_axis, None,
                                     axis_for_role(mesh, "theta")))
    n_args = 11 if depth == "adaptive" else 9
    in_sh = tuple(theta_shardings(mesh) if i == 1 else cols if i == 2
                  else req for i in range(n_args))
    out_sh = req if cols is req else {
        "W": cols, "loss_per_layer": req, "acc_per_layer": req,
        "final_loss": req, "final_acc": req}
    return in_sh, out_sh


def slot_shardings(mesh, depth="fixed"):
    """Where one request's padded slot (S, W0, Xl, Yl, Xte, Yte[, Xp,
    Yp]) lives between ``submit`` and its tick on ``mesh``: on every
    device, with W0's columns split over the 'theta' axis as the solver
    takes them, so that stacking a batch moves no slot between
    devices."""
    rep = replicated(mesh)
    w0 = (NamedSharding(mesh, P(None, axis_for_role(mesh, "theta")))
          if theta_split(mesh) > 1 else rep)
    n = 8 if depth == "adaptive" else 6
    return tuple(w0 if i == 1 else rep for i in range(n))


@functools.lru_cache(maxsize=64)
def tick_bytes(cfg: SURFConfig, bucket, max_batch, mesh=None, task=None):
    """(theta_bytes, gather_bytes) of one call of a bucket executable, per
    device, from the shapes: the bytes of θ's float32 block the device
    streams through its layers, and the bytes it receives from the
    all-gathers of W's columns (L + 1 of them, for its block of request
    slots; 0 without a θ split). Counted once a (bucket, layout)."""
    task = resolve_task(cfg, task)
    split = theta_split(mesh)
    cols = padded_columns(task.dim, split)
    din = U.perceptron_in_dim(cfg, task)
    L_ = cfg.n_layers
    theta = 4 * L_ * ((din + 1) * cols // split + cfg.filter_taps + 1)
    slots = max_batch // (1 if mesh is None else
                          _axis_size(mesh, axis_for_role(mesh, "agent")))
    gather = (4 * (L_ + 1) * slots * int(bucket.n_agents) * cols
              * (split - 1) // split)
    return theta, gather


def make_bucket_solver(cfg: SURFConfig, bucket, max_batch, *,
                       activation="relu", mix_fn=None, task=None,
                       cache=None, depth="fixed", mesh=None):
    """The jitted request-batched solver for one shape bucket.

    ``depth="fixed"`` (default): vmap-of-scan ``solve(S (B,n,n), theta,
    W0 (B,n,d), Xl (B,L,n,b,F), Yl (B,L,n,b), Xte (B,n,t,F),
    Yte (B,n,t), mask (B,n), t_real (B,))`` → per-request metric stacks
    with a leading (B,) axis.

    ``depth="adaptive"``: the shared early-exit while-loop
    (``_serve_core_adaptive``) — same signature with probe arrays
    ``Xp (B,n,p,F), Yp (B,n,p)`` inserted after Yte, and a ``depth``
    (B,) field in the result.

    ``mesh`` shards the request axis over the mesh's agent-role axis
    (``request_shardings``): a bucket's (B, n_pad, ...) stacked cohorts
    split over devices, zero collectives per request; a 'theta' axis
    splits θ's columns (module doc), W0 and W then having
    ``surf_rules.padded_columns`` columns. The split is a
    ``shard_map``, not a partitioner decision: the TPU compiler cannot
    partition a Pallas (Mosaic) kernel on its own, so ``mix="pallas"``
    needs each device handed its local block.

    ``cache`` (a ``BoundedLRU``) memoizes the executable under
    ``serve_cache_key``."""
    def build():
        if depth == "adaptive":
            solve = _serve_core_adaptive(cfg, activation, mix_fn=mix_fn,
                                         task=task)
        else:
            theta_axis = None
            if mesh is not None and theta_split(mesh) > 1:
                theta_axis = axis_for_role(mesh, "theta")
            solve = jax.vmap(
                _serve_core(cfg, activation, mix_fn=mix_fn, task=task,
                            theta_axis=theta_axis),
                in_axes=(0, None, 0, 0, 0, 0, 0, 0, 0))
        if mesh is None:
            return jax.jit(solve)
        in_sh, out_sh = request_shardings(mesh, max_batch, depth)
        spec = functools.partial(jax.tree_util.tree_map, lambda s: s.spec)
        # jax has no varying-axis rule for pallas_call (as in
        # topology.halo's Pallas resident), and types all_gather's result
        # as varying, though the scores read from the gathered W are the
        # same on every θ block; the dense unsplit path keeps the check
        solve = jax.shard_map(solve, mesh=mesh, in_specs=spec(in_sh),
                              out_specs=spec(out_sh),
                              check_vma=(mix_fn is None
                                         and theta_split(mesh) == 1))
        return jax.jit(solve, in_shardings=in_sh, out_shardings=out_sh)

    if cache is None:
        return build()
    key = serve_cache_key(cfg, bucket, max_batch, activation,
                          mix_fn=mix_fn, task=task, depth=depth, mesh=mesh)
    if key is None:
        return build()
    return cache.get_or_build(key, build)
