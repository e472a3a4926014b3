"""Shared core of the streaming SURF engine: the S-as-argument meta-step
and evaluation bodies (paper Algorithm 1 + Figure 3), the ``TrainState``
carried through every scan, and the compiled-engine cache keys.

Each meta-step: sample one downstream dataset D_q, sample W_0 ~ N(μ0, σ0²I)
and L per-layer mini-batches from D_q's training examples, run the unrolled
network, evaluate the test loss f(W_L) on D_q's held-out examples, add the
λ-weighted descending-constraint slacks, take an ADAM step on θ (eq. 6) and
a projected ascent step on λ (eq. 7).

Keeping S OUT of the closures (``meta_step_s(S, state, batch, key)``,
``evaluate_s(S, theta, batch, key)``) lets one jitted engine serve every
topology/seed of the same config — S rides through jit as a device
argument. The drivers live in ``engine.scan`` (single-seed streaming
scan), ``engine.seeds`` (seed-batched outer vmap), ``engine.snapshots``
(in-scan evaluation) and ``engine.resume`` (donate-through-checkpoint).

``mix_fn`` replaces the dense graph filter with a collective-efficient
exchange (``core.ring.make_ring_mix`` / ``topology.halo.make_halo_mix``).
A SCHEDULED mixer (``topology.halo.make_scheduled_halo_mix``, marked by
``.scheduled = True``) is selected per meta-step by the CARRIED
``state.step`` — ``mix_fn.at_step(state.step)`` returns the step-t filter
— so banded time-varying schedules keep the ppermute collective-bytes
savings instead of falling back to dense ``S_t @ W``. A SEED-BATCHED
mixer (``topology.halo.make_seed_halo_mix``, ``.seed_batched = True``)
is bound per seed LANE: ``engine.seeds`` vmaps ``meta_step_s`` over its
stacked per-seed blocks (the optional ``mix_blocks`` argument) with
``spmd_axis_name='seed'``, so the halo ppermutes run over the agent
sub-axis of a 2-D ('seed', 'agent') mesh while seeds stay sharded.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import SURFConfig
from repro.core import constraints as C
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.optim import adam, apply_updates, clip_by_global_norm
from repro.topology.schedule import TopologySchedule
from repro.utils.cache import BoundedLRU

# Incremented each time a meta_step / eval / serve body is TRACED (not
# executed) — the scan engines' contract is that an entire training run
# (seed-batched or not, scheduled or not, with or without in-scan
# snapshots) traces meta_step at most twice (once for the scan, possibly
# once for a standalone jit), the multi-seed evaluator's is that one
# batched evaluate call traces the body exactly once regardless of seed
# count, and the serving layer's is one trace per warm shape bucket
# (``serve.buckets``; replaying requests through warm buckets adds zero).
# "adaptive" counts traces of the early-exit while-loop solve bodies
# (``_adaptive_eval_core`` + the adaptive serve core) — one per distinct
# (config, exit params, shape), zero on cache hits.
TRACE_COUNTS = {"meta_step": 0, "eval": 0, "serve": 0, "adaptive": 0}


class TrainState(NamedTuple):
    theta: dict
    lam: jnp.ndarray
    opt_state: dict
    step: jnp.ndarray


def init_state(key, cfg: SURFConfig, init="dgd", task=None):
    theta = U.init_udgd(key, cfg, init=init, task=task)
    opt = adam(cfg.lr_theta)
    return TrainState(theta=theta, lam=jnp.zeros((cfg.n_layers,)),
                      opt_state=opt.init(theta), step=jnp.zeros((), jnp.int32))


def _meta_step_core(cfg: SURFConfig, constrained, activation, star, mix_fn,
                    task=None):
    """S-as-argument meta step: ``meta_step_s(S, state, batch, key)`` and
    ``forward_s(S, theta, W0, Xl, Yl)``. Keeping S out of the closure lets
    one jitted engine serve every topology/seed of the same config.

    A scheduled ``mix_fn`` (``.scheduled`` attribute) is re-bound every
    call via ``mix_fn.at_step(state.step)`` — the carried step counter
    selects the step-t coefficient blocks, so checkpoint-restored states
    resume the exact mixing stream.

    ``task`` is the inner problem (``core.tasks``); None resolves the
    config's task (legacy classification by default). The body only calls
    the Task interface — no task-specific branches live here."""
    task = resolve_task(cfg, task)
    opt = adam(cfg.lr_theta)
    use_star = cfg.topology == "star" if star is None else star
    layer_fn = U.udgd_layer_star if use_star else U.udgd_layer
    seed_batched = bool(getattr(mix_fn, "seed_batched", False))
    scheduled = (bool(getattr(mix_fn, "scheduled", False))
                 and not seed_batched)
    static_mix = None if (scheduled or seed_batched) else mix_fn
    # RSDUN robust constraints: an extra perturbation key is split off the
    # step key ONLY when enabled, so the default path's RNG stream (and
    # therefore its trajectory) is untouched.
    robust = cfg.robust_sigma > 0.0 and cfg.robust_samples > 0

    def _forward(S, theta, W0, Xl, Yl, mf):
        def body(W, xs):
            p_l, Xb, Yb = xs
            Wn = layer_fn(p_l, S, W, Xb, Yb, cfg, activation, mix_fn=mf,
                          task=task)
            return Wn, Wn
        W_L, Ws = jax.lax.scan(body, W0, (theta, Xl, Yl))
        return W_L, jnp.concatenate([W0[None], Ws], axis=0)

    def forward_s(S, theta, W0, Xl, Yl):
        if scheduled or seed_batched:
            raise ValueError(
                "forward_s has no step counter / seed lane to bind a "
                "scheduled or seed-batched mix_fn — pass a statically "
                "bound filter, or use the meta step (which binds the "
                "carried state.step and, in engine.seeds, the lane's "
                "blocks)")
        return _forward(S, theta, W0, Xl, Yl, static_mix)

    def lagrangian_fn(theta, lam, S, W0, Xl, Yl, Xte, Yte, mf, kp):
        W_L, W_all = _forward(S, theta, W0, Xl, Yl, mf)
        test_loss = task.fl_loss(W_L, Xte, Yte)
        with jax.named_scope("surf/constraints"):
            gnorms = C.layer_grad_norms(W_all, Xl, Yl, cfg, task=task)
            if robust:
                g_rob = C.robust_layer_grad_norms(W_all, Xl, Yl, cfg, kp,
                                                  task=task, nominal=gnorms)
                slack = C.robust_slacks(g_rob, gnorms, cfg.eps)
            else:
                slack = C.slacks(gnorms, cfg.eps)
            lag = (C.lagrangian(test_loss, lam, slack) if constrained
                   else test_loss)
        return lag, (test_loss, slack, gnorms, W_L)

    def meta_step_s(S, state: TrainState, batch, key, mix_blocks=None):
        """batch: dict with Xtr (n,m,F), Ytr (n,m), Xte (n,t,F), Yte (n,t).
        ``mix_blocks``: ONE seed lane's coefficient blocks for a
        seed-batched mixer — supplied by the engine-side vmap in
        ``engine.seeds`` (in_axes=0 over ``mix_fn.blocks``), unused
        otherwise."""
        TRACE_COUNTS["meta_step"] += 1
        if seed_batched:
            mf = mix_fn.bind(mix_blocks, state.step)
        elif scheduled:
            mf = mix_fn.at_step(state.step)
        else:
            mf = mix_fn
        if robust:
            kw, kb, kp = jax.random.split(key, 3)
        else:
            kw, kb = jax.random.split(key)
            kp = None
        W0 = U.sample_w0(kw, cfg, task=task)
        Xl, Yl = U.sample_layer_batches(kb, batch["Xtr"], batch["Ytr"], cfg)
        (lag, (tl, slack, gnorms, W_L)), grads = jax.value_and_grad(
            lagrangian_fn, has_aux=True)(state.theta, state.lam, S, W0, Xl,
                                         Yl, batch["Xte"], batch["Yte"], mf,
                                         kp)
        with jax.named_scope("surf/clip"):
            grads, gn = clip_by_global_norm(grads, 10.0)
        with jax.named_scope("surf/adam"):
            upd, opt_state = opt.update(grads, state.opt_state)
            theta = apply_updates(state.theta, upd)
        with jax.named_scope("surf/dual"):
            lam = (C.dual_ascent(state.lam, slack, cfg.lr_lambda)
                   if constrained else state.lam)
        test_acc = task.fl_metric(W_L, batch["Xte"], batch["Yte"])
        metrics = {"lagrangian": lag, "test_loss": tl, "test_acc": test_acc,
                   "slack_max": jnp.max(slack), "slack_mean": jnp.mean(slack),
                   "gnorm_first": gnorms[0], "gnorm_last": gnorms[-1],
                   "grad_norm": gn, "lam_sum": jnp.sum(lam)}
        return TrainState(theta, lam, opt_state, state.step + 1), metrics

    return meta_step_s, forward_s


def _reject_seed_batched_mix(mix_fn, where):
    """Single-seed builders can't bind a seed-batched mixer (its blocks
    are vmapped per lane by ``engine.seeds``) — point the caller at the
    seed-batched engine instead."""
    if getattr(mix_fn, "seed_batched", False):
        raise ValueError(
            f"{where} is a single-seed builder but got a SEED-BATCHED "
            "mixer (topology.halo.make_seed_halo_mix) — its per-seed "
            "blocks are bound by the engine vmap in engine.seeds; pass "
            "it to train_surf(seeds=...)/make_seed_train_scan, or build "
            "a static make_halo_mix / make_ring_mix here")


def _check_static_s(S, where):
    """The static-S builders can't consume a time-varying schedule —
    point the caller at the schedule-aware drivers instead."""
    if isinstance(S, TopologySchedule):
        raise TypeError(
            f"{where} needs a static (n, n) mixing matrix, got a "
            "TopologySchedule — pass a schedule to train_scan/train "
            "(and evaluate on a static S, e.g. schedule.S[t])")


def make_meta_step(cfg: SURFConfig, S, *, constrained=True,
                   activation="relu", star=None, mix_fn=None, jit=True,
                   task=None):
    """Build the meta-training step (jitted unless ``jit=False`` — the scan
    engine embeds the raw body in its own jit).

    ``constrained=False`` gives the ablation of Appendix D (λ frozen at 0).
    ``star``: override star-topology handling (defaults to cfg.topology).
    ``mix_fn``: override the dense graph filter (ring/halo ppermute path;
    a scheduled mixer is legal here too — it indexes its own stacked
    blocks by ``state.step`` and ignores the static ``S``).
    ``task``: inner problem override (``core.tasks``); None resolves cfg.
    """
    _check_static_s(S, "make_meta_step")
    _reject_seed_batched_mix(mix_fn, "make_meta_step")
    meta_step_s, forward_s = _meta_step_core(cfg, constrained, activation,
                                             star, mix_fn, task)

    def meta_step(state, batch, key):
        return meta_step_s(S, state, batch, key)

    def forward(theta, W0, Xl, Yl):
        return forward_s(S, theta, W0, Xl, Yl)

    return (jax.jit(meta_step) if jit else meta_step), forward


def _eval_core(cfg: SURFConfig, activation, star, mix_fn=None, task=None):
    """S-as-argument evaluation body ``evaluate_s(S, theta, batch, key)`` —
    keeping S out of the closure lets ``core.surf`` cache one jitted vmapped
    evaluator per config across topologies/seeds, and ``engine.snapshots``
    embed the same body inside the training scan. ``mix_fn`` replaces the
    dense graph filter (ring ppermute path), same contract as the trainer.
    The ``acc`` slots carry ``task.fl_metric`` (accuracy / NMSE)."""
    task = resolve_task(cfg, task)
    use_star = cfg.topology == "star" if star is None else star
    layer_fn = U.udgd_layer_star if use_star else U.udgd_layer

    def evaluate_s(S, theta, batch, key):
        TRACE_COUNTS["eval"] += 1
        W0, Xl, Yl = U.featurize_cohort(key, batch, cfg, task=task)

        def body(W, xs):
            p_l, Xb, Yb = xs
            Wn = layer_fn(p_l, S, W, Xb, Yb, cfg, activation, mix_fn=mix_fn,
                          task=task)
            loss = task.fl_loss(Wn, batch["Xte"], batch["Yte"])
            acc = task.fl_metric(Wn, batch["Xte"], batch["Yte"])
            return Wn, (loss, acc)
        W_L, (losses, accs) = jax.lax.scan(body, W0, (theta, Xl, Yl))
        return {"loss_per_layer": losses, "acc_per_layer": accs,
                "final_loss": losses[-1], "final_acc": accs[-1]}

    return evaluate_s


def _adaptive_eval_core(cfg: SURFConfig, activation, star, mix_fn=None,
                        task=None):
    """S-as-argument ADAPTIVE-depth evaluation body: same contract as
    ``_eval_core`` but the unroll runs under the early-exit while loop
    (``core.unroll.udgd_forward_adaptive``) — layers stop once the
    probe-batch grad-norm ratio plateaus at 1 − ``cfg.exit_threshold``.
    No per-layer metric stacks (a while loop has no fixed output axis);
    returns the final loss/metric plus the realized ``depth``. With
    ``cfg.exit_threshold == 0`` the body runs all L layers and matches
    ``_eval_core``'s final row exactly (same pre-sampled layer batches,
    same layer math)."""
    task = resolve_task(cfg, task)
    use_star = cfg.topology == "star" if star is None else star
    layer_fn = U.udgd_layer_star if use_star else U.udgd_layer

    def evaluate_s(S, theta, batch, key):
        TRACE_COUNTS["adaptive"] += 1
        W0, Xl, Yl = U.featurize_cohort(key, batch, cfg, task=task)
        Xp, Yp = U.probe_batch(batch, cfg)
        W_L, depth = U.udgd_forward_adaptive(
            theta, S, W0, Xl, Yl, Xp, Yp, cfg, activation, mix_fn=mix_fn,
            task=task, layer_fn=layer_fn)
        loss = task.fl_loss(W_L, batch["Xte"], batch["Yte"])
        acc = task.fl_metric(W_L, batch["Xte"], batch["Yte"])
        return {"final_loss": loss, "final_acc": acc,
                "depth": depth.astype(jnp.float32)}

    return evaluate_s


def adaptive_variant(cfg: SURFConfig, base):
    """Cache-key variant tag for an adaptive-depth computation: the
    normalizer scrubs the exit fields from cfg (fixed-depth engines
    ignore them), so every adaptive builder must carry them HERE — two
    thresholds trace different while-loop bodies."""
    return (base + "-adaptive", float(cfg.exit_threshold),
            int(cfg.min_layers), int(cfg.probe_size))


def make_eval(cfg: SURFConfig, S, *, activation="relu", star=None, jit=True,
              mix_fn=None, task=None):
    """Per-layer loss/accuracy trajectory on a downstream dataset — the
    evaluation used for every paper figure. ``jit=False`` returns the raw
    body for embedding under vmap (see ``core.surf.evaluate_surf``);
    ``mix_fn`` routes mixing through the ring ppermute filter."""
    _check_static_s(S, "make_eval")
    evaluate_s = _eval_core(cfg, activation, star, mix_fn, task)

    def evaluate(theta, batch, key):
        return evaluate_s(S, theta, batch, key)

    return jax.jit(evaluate) if jit else evaluate


# One compiled scan engine per distinct traced computation — the benchmarks
# call train_surf repeatedly with the same config and must not pay a
# re-trace/re-compile per experiment. S is a jit ARGUMENT, so every
# topology/seed of a config reuses the same executable. Bounded LRU
# (registered as "engine" — ``repro.clear_caches()``/``cache_stats()``):
# an evicted engine recompiles on its next use. See ``engine/README.md``
# for the full key anatomy.
_ENGINE_CACHE = BoundedLRU(maxsize=64, name="engine")


def _mix_tag(mix_fn):
    """Hashable identity of a mix_fn for engine-cache keys. Tagged mixers
    (``core.ring.make_ring_mix`` / ``topology.halo`` set ``.tag``) cache
    normally; an untagged custom mix_fn returns None, which the engine
    builders treat as "don't cache" (the closure could compute anything)."""
    return getattr(mix_fn, "tag", None) if mix_fn is not None else ()


def _engine_cache_key(cfg: SURFConfig, variant, activation, star,
                      mesh=None, mix_fn=None, task=None):
    """Normalize cfg to the fields that shape the traced computation: on the
    non-star path the topology/degree/er_p fields only affect how S was
    BUILT (S itself is a jit argument), so 'regular' and 'er' experiments
    share one executable. The star path reads cfg.topology inside
    ``star_filter_mask`` and keeps the full config. ``variant`` is an
    arbitrary hashable tag distinguishing computations the other fields
    don't ("train"/constrained, "train-seeds"/n_seeds, "eval", "async",
    snapshot cadence).

    The full key is (cfg, variant, activation, star, mesh-fingerprint,
    mix-tag, task-tag): engines lowered with different explicit shardings,
    a different ring geometry, or a different inner problem
    (``resolve_task(cfg, task).cache_tag``) are different executables.
    Returns None (uncacheable) for an untagged custom ``mix_fn``."""
    import dataclasses
    from repro.sharding.surf_rules import mesh_fingerprint
    mt = _mix_tag(mix_fn)
    if mt is None:
        return None
    task_tag = resolve_task(cfg, task).cache_tag
    use_star = cfg.topology == "star" if star is None else star
    if not use_star:
        cfg = dataclasses.replace(cfg, topology="regular", degree=0,
                                  er_p=0.0)
    # The adaptive-depth exit fields only shape the EARLY-EXIT solve
    # bodies, which carry them in their variant tag (``adaptive_variant``)
    # — scrub them here so fixed-depth engines are shared across
    # exit_threshold sweeps.
    cfg = dataclasses.replace(cfg, exit_threshold=0.0, min_layers=1,
                              probe_size=0)
    return (cfg, variant, activation, use_star, mesh_fingerprint(mesh), mt,
            task_tag)
