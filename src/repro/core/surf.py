"""Public SURF API: build the FL problem, meta-train U-DGD, evaluate, and
the asynchronous-agent perturbation study (paper App. D).

Meta-training defaults to the fully-jitted ``repro.engine`` scan (one
compiled scan per experiment); ``engine="python"`` keeps the step-wise
loop, and ``mix_fn``/``mesh`` route mixing through the ring ppermute path
on an agent-axis-sharded mesh. ``train_surf(seeds=...)`` trains a whole
BATCH of init/topology seeds in one compiled executable
(``engine.seeds``), and ``eval_every`` folds held-out evaluation
snapshots into the scan (``engine.snapshots``) — the train-side mirrors
of the multi-seed evaluation layer below. Evaluation over downstream
datasets is a single vmapped+jitted computation — a batch of seeds adds
an OUTER vmap over evaluation keys, so robustness protocols that need
many seeds per config (Hadou et al. 2023) compile once and return
(n_seeds, ...) metric stacks instead of re-dispatching per seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as TR
from repro.configs.base import SURFConfig
from repro.core import unroll as U
from repro.core.tasks import (classification_task, resolve_task,  # noqa: F401
                              sparse_recovery_task)
from repro.data.pipeline import stack_meta_datasets
from repro.topology import families as G
from repro.utils.cache import BoundedLRU


def make_problem(cfg: SURFConfig, seed=0):
    """Returns (adjacency, mixing matrix S as jnp array)."""
    A, S = G.build_topology(cfg.topology, cfg.n_agents, degree=cfg.degree,
                            p=cfg.er_p, seed=seed)
    return A, jnp.asarray(S, jnp.float32)


SCENARIOS = ("static", "link-failure", "dropout", "markov", "anneal")


def make_scenario(cfg: SURFConfig, scenario, steps, seed=0, *,
                  p_fail=0.2, n_drop=None, p_drop=0.05, p_recover=0.5):
    """Named training scenario → ``TopologySchedule`` over the config's
    base graph (None for "static"/None — train on the static S).

      * "link-failure": each link down i.i.d. w.p. ``p_fail`` per step,
      * "dropout": ``n_drop`` agents (default n/10) drop out per step,
      * "markov": bursty link outages (``p_drop``/``p_recover`` chain),
      * "anneal": ring→random Watts–Strogatz rewiring curriculum.

    The schedule length is ``steps`` (one S_t per meta-step; the engine
    cycles mod T if trained longer)."""
    from repro.topology import schedule as SCH
    if scenario in (None, "static"):
        return None
    A, _ = G.build_topology(cfg.topology, cfg.n_agents, degree=cfg.degree,
                            p=cfg.er_p, seed=seed)
    if scenario == "link-failure":
        return SCH.link_failure_schedule(A, steps, p_fail=p_fail, seed=seed)
    if scenario == "dropout":
        nd = n_drop if n_drop is not None else max(1, cfg.n_agents // 10)
        return SCH.dropout_schedule(A, steps, n_drop=nd, seed=seed)
    if scenario == "markov":
        return SCH.markov_link_schedule(A, steps, p_drop=p_drop,
                                        p_recover=p_recover, seed=seed)
    if scenario == "anneal":
        return SCH.ring_to_random_anneal(cfg.n_agents, steps,
                                         k=max(2, 2 * (cfg.degree // 2)),
                                         seed=seed)
    raise ValueError(f"unknown scenario {scenario!r}; one of {SCENARIOS}")


MIXES = (None, "dense", "pallas", "ring", "halo", "halo-pallas")


def _resolve_mix(mix, mesh, cfg, *, S=None, schedule=None, S_stack=None):
    """Build the ``mix_fn`` named by a ``mix=`` string against the run's
    actual topology stack and the mesh's AGENT-role axis — exactly one of
    ``S`` (single-seed static), ``schedule`` (single-seed time-varying)
    or ``S_stack`` (seed-batched, static (n_seeds, n, n) or schedule
    (n_seeds, T, n, n)) describes the run.

    ``"pallas"`` is the DENSE path through the fused Pallas graph-filter
    kernel (``kernels.graph_filter.make_pallas_mix``): S stays a jit
    argument, so it needs no mesh and composes with schedules and seed
    batches like the dense matmul. ``"halo-pallas"`` keeps the halo
    ``ppermute`` boundary exchange but runs each shard's RESIDENT block
    through the same kernel (``topology.halo`` ``resident="pallas"``)."""
    if mix in (None, "dense"):
        return None
    if mix not in MIXES:
        raise ValueError(f"mix must be one of {MIXES}, got {mix!r}")
    if mix == "pallas":
        from repro.kernels.graph_filter import make_pallas_mix
        return make_pallas_mix()
    if mesh is None:
        raise ValueError(
            f"mix={mix!r} needs mesh= (the mesh whose agent axis the "
            "ppermute exchange runs over — launch.mesh.make_surf_mesh); "
            "for the meshless dense kernel path use mix='pallas'")
    from repro.sharding.surf_rules import axis_for_role
    axis = axis_for_role(mesh, "agent")
    if mix == "ring":
        if cfg.topology != "ring":
            raise ValueError("mix='ring' needs cfg.topology='ring' (the "
                             "circulant special case); use mix='halo' "
                             "for arbitrary topologies")
        if schedule is not None or S_stack is not None:
            raise ValueError("mix='ring' bakes one static circulant — "
                             "use mix='halo' for schedules or "
                             "seed-batched runs")
        from repro.core.ring import make_ring_mix
        return make_ring_mix(mesh, axis, cfg.n_agents,
                             max(1, cfg.degree // 2))
    from repro.topology.halo import (make_halo_mix, make_scheduled_halo_mix,
                                     make_seed_halo_mix)
    resident = "pallas" if mix == "halo-pallas" else "dense"
    if S_stack is not None:
        # pass the stack OBJECT through: the mixer weakrefs it, so the
        # engine's content-digest guard short-circuits on identity
        # instead of re-hashing the full per-seed stack
        return make_seed_halo_mix(mesh, axis, S_stack, resident=resident)
    if schedule is not None:
        return make_scheduled_halo_mix(mesh, axis, schedule,
                                       resident=resident)
    return make_halo_mix(mesh, axis, np.asarray(S), resident=resident)


def train_surf(cfg: SURFConfig, meta_datasets, steps, seed=0,
               constrained=True, activation="relu", log_every=10,
               init="dgd", engine="scan", mix_fn=None, mix=None, mesh=None,
               scenario=None, schedule=None, seeds=None, eval_every=0,
               eval_datasets=None, checkpoint_every=0, checkpoint_dir=None,
               task=None, q_sharded=False):
    """Meta-train U-DGD on the config's topology. ``scenario`` (a name
    from ``SCENARIOS``) or ``schedule`` (an explicit
    ``TopologySchedule``) trains under TIME-VARYING graphs — the
    returned S stays the static base mixing matrix, which evaluation
    uses (robustness protocols train on perturbed topologies and test
    on the nominal one).

    ``seeds``: optional batch of TRAINING seeds — ONE compiled
    seed-batched engine (``engine.seeds``) trains every seed with its
    own init/RNG/topology (and its own per-seed perturbation stream
    under a scenario); the returned state/history/S gain a leading
    (n_seeds,) axis and row i matches the sequential ``seed=seeds[i]``
    run. ``mesh`` shards the SEED role; on a 2-D ('seed', 'agent') mesh
    (``launch.mesh.make_surf_mesh``) ``mix="halo"`` additionally routes
    mixing through the halo ``ppermute`` exchange over the agent
    sub-axis — both axes from one compiled scan.

    ``mix``: convenience string building the right mixer for the run —
    "dense"/None (matmul path), "pallas" (the dense filter fused into
    the Pallas graph-filter kernel, ``kernels.graph_filter`` — no mesh
    needed, composes with schedules/seeds exactly like dense), "ring"
    (circulant ``ppermute``, single-seed static ring only), "halo"
    (block-sparse exchange; composes with schedules via the scheduled
    mixer and with ``seeds`` via the seed-batched mixer) or
    "halo-pallas" (halo boundary exchange + Pallas-resident on-shard
    block). Mutually exclusive with an explicit ``mix_fn``.

    ``eval_every``: fold held-out evaluation snapshots into the scan
    every that many meta-steps (``engine.snapshots``; needs
    ``eval_datasets``, evaluated against the NOMINAL static S). Adds a
    ``snapshots`` list to the return:
    (state, hist, snapshots, S) / (states, hist, snapshots, S_stack).

    ``checkpoint_every``/``checkpoint_dir``: PERIODIC in-scan
    checkpointing — the carried state is written at the cadence via an
    ``io_callback`` without leaving the compiled scan: ``ckpt_<step>``
    payloads for the single-seed engine
    (``engine.resume.resume_train_scan`` restores bit-exactly) and
    ``ckpt_<step>/seeds`` stacked per-seed payloads when combined with
    ``seeds=`` (``engine.resume.resume_train_scan_seeds``).

    ``task``: the inner FL problem (a ``core.tasks`` Task object, e.g.
    ``classification_task(cfg)`` / ``sparse_recovery_task(...)``); None
    resolves ``cfg.task`` (legacy classification by default). Every
    engine path — dense/ring/halo mixers, schedules, seed batching —
    is task-generic.

    ``q_sharded``: shard the meta-training pool's Q axis over the mesh's
    agent-role axis (memory-capacity mode for big pools — each device
    holds Q/P datasets; dense/pallas mixing only, see
    ``engine.scan.make_train_scan``). Requires ``mesh``; with ``seeds``
    the mesh must be 2-D ('seed', 'agent')."""
    if engine not in ("scan", "python"):
        raise ValueError(f"engine must be 'scan' or 'python', got {engine!r}")
    if mesh is not None and engine != "scan":
        raise ValueError("mesh shardings require engine='scan' (the "
                         "step-wise python driver is unsharded)")
    if scenario is not None and schedule is not None:
        raise ValueError("pass either scenario= (a name) or schedule= "
                         "(an explicit TopologySchedule), not both")
    if mix is not None and mix_fn is not None:
        raise ValueError("pass either mix= (a name the right mixer is "
                         "built from) or mix_fn= (an explicit mixer), "
                         "not both")
    if mix is not None and mix not in MIXES:
        raise ValueError(f"mix must be one of {MIXES}, got {mix!r}")
    if eval_every:
        if engine != "scan":
            raise ValueError("eval_every (in-scan snapshots) requires "
                             "engine='scan'")
        if eval_datasets is None:
            raise ValueError("eval_every > 0 needs eval_datasets (the "
                             "held-out snapshot pool)")
    if checkpoint_every:
        if engine != "scan":
            raise ValueError("checkpoint_every (periodic in-scan "
                             "checkpointing) requires engine='scan'")
        if not checkpoint_dir:
            raise ValueError("checkpoint_every > 0 needs checkpoint_dir")
    if seeds is not None:
        if engine != "scan":
            raise ValueError("seed batching requires engine='scan'")
        if seed != 0:
            raise ValueError(
                "pass either seed= (one run) or seeds= (a seed-batched "
                "run), not both — the batch defines every per-seed "
                "init/topology/RNG stream")
        if (mix_fn is not None
                and not getattr(mix_fn, "seed_batched", False)
                and not getattr(mix_fn, "takes_S", False)):
            raise ValueError(
                "seed-batched training needs a SEED-BATCHED mixer "
                "(topology.halo.make_seed_halo_mix / mix='halo'), an "
                "S-as-argument mixer (kernels.graph_filter."
                "make_pallas_mix / mix='pallas') or the dense path — a "
                "static mix_fn bakes one topology and would silently "
                "override the per-seed S_i stream")
        seed_list = [int(s) for s in seeds]
        S_stack = jnp.stack([make_problem(cfg, s)[1] for s in seed_list])
        if schedule is not None:
            S_train = jnp.broadcast_to(
                schedule.S, (len(seed_list),) + schedule.S.shape)
        elif scenario not in (None, "static"):
            S_train = TR.stack_schedules(
                [make_scenario(cfg, scenario, steps, s) for s in seed_list])
        else:
            S_train = S_stack
        if mix is not None:
            mix_fn = _resolve_mix(mix, mesh, cfg, S_stack=S_train)
        out = TR.train_scan_seeds(
            cfg, S_train, meta_datasets, steps, seed_list,
            constrained=constrained, activation=activation,
            log_every=log_every, init=init, mesh=mesh, mix_fn=mix_fn,
            eval_every=eval_every, eval_datasets=eval_datasets,
            S_eval_stack=S_stack if eval_every else None,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, task=task, q_sharded=q_sharded)
        return (*out, S_stack)
    _, S = make_problem(cfg, seed)
    if schedule is None:
        schedule = make_scenario(cfg, scenario, steps, seed)
    S_train = schedule if schedule is not None else S
    if mix is not None:
        mix_fn = _resolve_mix(mix, mesh, cfg, S=S, schedule=schedule)
    key = jax.random.PRNGKey(seed)
    if engine == "scan":
        kw = {"mix_fn": mix_fn, "mesh": mesh, "eval_every": eval_every,
              "eval_datasets": eval_datasets,
              "checkpoint_every": checkpoint_every,
              "checkpoint_dir": checkpoint_dir, "q_sharded": q_sharded}
        if eval_every:
            kw["S_eval"] = S
    elif q_sharded:
        raise ValueError("q_sharded=True requires engine='scan' (the "
                         "step-wise python driver is unsharded)")
    else:
        kw = {"mix_fn": mix_fn}
    driver = TR.train_scan if engine == "scan" else TR.train
    out = driver(cfg, S_train, meta_datasets, steps, key,
                 constrained=constrained, activation=activation,
                 log_every=log_every, init=init, task=task, **kw)
    return (*out, S)


def _eval_keys(base_key, n):
    return jax.vmap(lambda i: jax.random.fold_in(base_key, i))(jnp.arange(n))


# Jitted vmapped evaluators cached with S as a jit argument — benchmark
# loops evaluate many times with identical shapes and must not re-trace per
# call. Keys share trainer._engine_cache_key's normalization so non-star
# topology variants (which only differ in how S was built) reuse one
# executable; the key also carries the mesh fingerprint and mix tag (see
# trainer._engine_cache_key), so ring-mix evaluators don't collide with
# dense ones. An untagged custom mix_fn is uncacheable and rebuilt per
# call. Both caches are bounded LRUs registered for
# ``repro.clear_caches()`` / ``cache_stats()``.
_EVAL_CACHE = BoundedLRU(maxsize=64, name="surf-eval")
_ASYNC_CACHE = BoundedLRU(maxsize=32, name="surf-async")


DEPTHS = ("fixed", "adaptive")


def _resolve_depth(cfg, depth):
    """Normalize the ``depth=`` opt-in of the solve paths: None means
    fixed-L (the paper's forward), "adaptive" selects the early-exit
    while-loop solver configured by cfg.exit_threshold / min_layers /
    probe_size."""
    depth = "fixed" if depth is None else depth
    if depth not in DEPTHS:
        raise ValueError(f"depth must be one of {DEPTHS}, got {depth!r}")
    if depth == "adaptive" and cfg.min_layers > cfg.n_layers:
        raise ValueError(
            f"min_layers={cfg.min_layers} exceeds n_layers={cfg.n_layers}")
    return depth


def _batched_eval(cfg: SURFConfig, activation, mix_fn=None, task=None,
                  depth="fixed"):
    """One compiled evaluator per config: inner vmap over the stacked
    dataset axis Q, OUTER vmap over a batch of evaluation keys — called
    with keys (n_seeds, Q, 2), returns (n_seeds, Q, ...) metric stacks.
    ``depth="adaptive"`` swaps in the early-exit while-loop body
    (``engine._adaptive_eval_core``; cfg's exit fields ride the variant
    tag so thresholds key apart)."""
    def build():
        core = (TR._adaptive_eval_core if depth == "adaptive"
                else TR._eval_core)
        ev_s = core(cfg, activation, None, mix_fn, task)
        per_q = jax.vmap(ev_s, in_axes=(None, None, 0, 0))
        return jax.jit(jax.vmap(per_q, in_axes=(None, None, None, 0)))
    variant = (TR.adaptive_variant(cfg, "eval") if depth == "adaptive"
               else "eval")
    key = TR._engine_cache_key(cfg, variant, activation, None, mix_fn=mix_fn,
                               task=task)
    if key is None:
        return build()
    return _EVAL_CACHE.get_or_build(key, build)


def _seed_batch(seed, seeds):
    """Normalize the (seed, seeds) pair: returns (array of seeds, whether
    the caller asked for a single unbatched seed)."""
    if seeds is None:
        return np.asarray([seed], np.int64), True
    arr = np.asarray(list(seeds), np.int64).reshape(-1)
    if arr.size == 0:
        raise ValueError("seeds must be non-empty")
    return arr, False


def evaluate_surf(cfg: SURFConfig, state, S, datasets, seed=0,
                  activation="relu", seeds=None, mix_fn=None, mesh=None,
                  task=None, depth=None):
    """Per-layer loss/acc trajectories averaged over downstream datasets —
    one vmapped computation over the stacked dataset axis.

    ``seeds``: optional batch of evaluation seeds. When given, a single
    compiled evaluator runs all seeds via an outer vmap over keys and
    every returned metric gains a leading (n_seeds,) axis — row i matches
    ``evaluate_surf(..., seed=seeds[i])`` exactly (same fold_in stream).
    ``mix_fn`` evaluates with the ring ppermute filter instead of S;
    ``mesh`` places the stacked pool with its Q axis sharded over 'data'
    (``sharding.surf_rules.stacked_q_sharding``) — data-parallel
    evaluation over downstream datasets.

    ``depth="adaptive"`` solves with the CONVERGENCE-ADAPTIVE early-exit
    unroll (``core.unroll.udgd_forward_adaptive``): layers stop once the
    probe-batch grad-norm ratio plateaus at 1 − ``cfg.exit_threshold``
    (≥ ``cfg.min_layers`` layers). The RNG stream is identical to the
    fixed path (same pre-sampled per-layer batches), so
    ``exit_threshold=0`` reproduces the fixed final row exactly. The
    return drops the per-layer stacks (a while loop has no fixed output
    axis) and instead carries ``final_loss``/``final_acc`` plus
    ``depth`` — the realized layer count averaged over datasets."""
    TR._check_static_s(S, "evaluate_surf")
    depth = _resolve_depth(cfg, depth)
    stacked = stack_meta_datasets(datasets)
    n_q = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if mesh is not None:
        from repro.sharding.surf_rules import stacked_q_sharding
        q_sh = stacked_q_sharding(mesh, n_q)
        stacked = jax.device_put(
            stacked, jax.tree_util.tree_map(lambda _: q_sh, stacked))
    seed_arr, single = _seed_batch(seed, seeds)
    keys = jnp.stack([_eval_keys(jax.random.PRNGKey(1000 + int(s)), n_q)
                      for s in seed_arr])
    outs = _batched_eval(cfg, activation, mix_fn, task,
                         depth=depth)(S, state.theta, stacked, keys)
    res = {k: np.asarray(v).mean(1) for k, v in outs.items()}
    return {k: v[0] for k, v in res.items()} if single else res


def solve_federation(cfg: SURFConfig, state, S, dataset, seed=0,
                     activation="relu", mix_fn=None, task=None, depth=None):
    """Solve ONE new federation with the trained model — the amortization
    primitive (paper §4) as a single call, and the reference the serving
    layer (``repro.serve``) is parity-tested against:
    ``FederationServer.submit(S, dataset, seed=seed)`` reproduces this
    result exactly (identical ``fold_in(PRNGKey(1000+seed), 0)`` RNG
    stream).  Reuses the cached ``evaluate_surf`` executable for the
    config (``cfg.n_agents`` must match the cohort). ``depth="adaptive"``
    solves with the early-exit unroll and adds the realized ``depth`` to
    the result — the reference for the adaptive serve path."""
    return evaluate_surf(cfg, state, S, [dataset], seed=seed,
                         activation=activation, mix_fn=mix_fn, task=task,
                         depth=depth)


def _async_core(cfg: SURFConfig, activation, task=None):
    """S-as-argument async-inference body (see ``make_async_run``)."""
    task = resolve_task(cfg, task)
    layer_fn = U.udgd_layer_star if cfg.topology == "star" else U.udgd_layer

    def run_s(S, theta, batch, key, async_mask):
        W0, Xl, Yl = U.featurize_cohort(key, batch, cfg, task=task)

        def body(carry, xs):
            W_prev, W = carry
            p_l, Xb, Yb = xs
            W_seen = jnp.where(async_mask[:, None], W_prev, W)
            Wn = layer_fn(p_l, S, W_seen, Xb, Yb, cfg, activation, task=task)
            # async agents also skip their own update this layer
            Wn = jnp.where(async_mask[:, None], W, Wn)
            loss = task.fl_loss(Wn, batch["Xte"], batch["Yte"])
            acc = task.fl_metric(Wn, batch["Xte"], batch["Yte"])
            return (W, Wn), (loss, acc)
        (_, W_L), (losses, accs) = jax.lax.scan(body, (W0, W0),
                                                (theta, Xl, Yl))
        return losses, accs

    return run_s


def make_async_run(cfg: SURFConfig, S, activation="relu", task=None):
    """Single-dataset async-inference body (paper Fig. 8): agents flagged in
    ``async_mask`` fail to update in sync — their neighbours consume the
    estimate communicated at the previous layer (one-layer-stale rows in
    the graph filter input). Unjitted; the batched path is
    ``evaluate_async``."""
    run_s = _async_core(cfg, activation, task)

    def run(theta, batch, key, async_mask):
        return run_s(S, theta, batch, key, async_mask)

    return run


def async_masks(cfg: SURFConfig, n_datasets, n_async, seed=0):
    """Per-dataset async-agent masks, (Q, n_agents) bool: each dataset gets
    its own uniformly-drawn set of ``n_async`` stale agents."""
    rng = np.random.default_rng(seed)
    masks = np.zeros((n_datasets, cfg.n_agents), bool)
    for q in range(n_datasets):
        masks[q, rng.choice(cfg.n_agents, n_async, replace=False)] = True
    return masks


def _batched_async(cfg: SURFConfig, activation, task=None):
    """One compiled async evaluator per config: inner vmap over datasets
    (per-dataset masks preserved), outer vmap over seed keys+masks —
    called with keys (n_seeds, Q, 2) and masks (n_seeds, Q, n)."""
    key = TR._engine_cache_key(cfg, "async", activation, None, task=task)

    def build():
        run_s = _async_core(cfg, activation, task)
        per_q = jax.vmap(run_s, in_axes=(None, None, 0, 0, 0))
        return jax.jit(jax.vmap(per_q, in_axes=(None, None, None, 0, 0)))

    return _ASYNC_CACHE.get_or_build(key, build)


def evaluate_async(cfg: SURFConfig, state, S, datasets, n_async, seed=0,
                   activation="relu", seeds=None, task=None, mesh=None):
    """Asynchronous communications (paper Fig. 8) over all downstream
    datasets in one vmapped computation, each dataset with its own mask.

    ``seeds``: optional batch of evaluation seeds — one outer-vmapped
    computation over (keys, masks); each seed draws its own per-dataset
    async masks and every returned metric gains a leading (n_seeds,)
    axis, row i matching ``evaluate_async(..., seed=seeds[i])``.
    ``mesh`` places the stacked pool with its Q axis sharded over the
    agent-role axis (``sharding.surf_rules.stacked_q_sharding``), exactly
    like ``evaluate_surf`` — the inner dataset vmap partitions over Q."""
    TR._check_static_s(S, "evaluate_async")
    stacked = stack_meta_datasets(datasets)
    n_q = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if mesh is not None:
        from repro.sharding.surf_rules import stacked_q_sharding
        q_sh = stacked_q_sharding(mesh, n_q)
        stacked = jax.device_put(
            stacked, jax.tree_util.tree_map(lambda _: q_sh, stacked))
    seed_arr, single = _seed_batch(seed, seeds)
    masks = jnp.stack([jnp.asarray(async_masks(cfg, n_q, n_async,
                                               seed=int(s)))
                       for s in seed_arr])
    keys = jnp.stack([_eval_keys(jax.random.PRNGKey(2000 + int(s)), n_q)
                      for s in seed_arr])
    losses, accs = _batched_async(cfg, activation, task)(
        S, state.theta, stacked, keys, masks)
    losses = np.asarray(losses).mean(1)      # (n_seeds, L)
    accs = np.asarray(accs).mean(1)
    if single:
        losses, accs = losses[0], accs[0]
    return {"loss_per_layer": losses, "acc_per_layer": accs,
            "final_loss": losses[..., -1], "final_acc": accs[..., -1]}
