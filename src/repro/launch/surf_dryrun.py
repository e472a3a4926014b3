"""Collective-byte counts of compiled SURF steps, for the sharded-lane
tests.

Each counter lowers one SURF step with the shardings the engine uses,
compiles it for the current mesh and sums the collectives of its
post-SPMD HLO (``launch.hlo_cost``):

  * ``meta_step_collective_bytes`` — one agent-sharded meta step, dense
    or through a ring/halo mixer (``tests/test_sharded_engine.py``,
    ``tests/test_engine.py``);
  * ``seed_meta_step_collective_bytes`` — one seed-batched meta step on
    a 2-D ('seed', 'agent') mesh (``tests/test_mesh2d.py``);
  * ``q_scan_collective_bytes`` — the Q-sharded scan engine per meta
    step (``tests/test_qsharded.py``).

``surf_batch_specs`` gives the batch shapes all three lower with
(``tests/test_tasks.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import engine as TR
from repro.launch import hlo_cost


def surf_batch_specs(cfg, task=None):
    """ShapeDtypeStructs of one meta-training batch (the Xtr/Ytr/Xte/Yte
    dict every SURF lowering harness needs) — single source of truth for
    the collective-byte counters below and the sharded-engine tests.
    ``task`` shapes the per-example feature dim and label dtype for
    non-default inner problems (``core.tasks``)."""
    from repro.core.tasks import resolve_task
    task = resolve_task(cfg, task)
    n, m, t, F_ = (cfg.n_agents, cfg.train_per_agent, cfg.test_per_agent,
                   task.feat_dim)
    ldt = task.label_dtype
    return {
        "Xtr": jax.ShapeDtypeStruct((n, m, F_), jnp.float32),
        "Ytr": jax.ShapeDtypeStruct((n, m), ldt),
        "Xte": jax.ShapeDtypeStruct((n, t, F_), jnp.float32),
        "Yte": jax.ShapeDtypeStruct((n, t), ldt),
    }


def meta_step_collective_bytes(cfg, S, mesh, mix_fn=None):
    """Per-META-STEP collective traffic of the agent-axis-sharded engine:
    lower ONE meta step (state/key replicated, batch agent-sharded) and
    parse its post-SPMD HLO. Returns (total collective bytes, per-kind
    dict) — independent of the scan trip count; the quantity the
    ring/halo ``mix_fn`` paths exist to shrink. ``mix_fn`` may be a
    SCHEDULED mixer (``topology.halo.make_scheduled_halo_mix``): the
    lowered step then binds the mixing blocks by the carried
    ``state.step`` and ``S`` is the (unused) static stand-in."""
    from repro.sharding.surf_rules import (agent_sharding, replicated,
                                           train_state_shardings)
    rep = replicated(mesh)
    agent_sh = agent_sharding(mesh, cfg.n_agents)
    state_spec = jax.eval_shape(lambda k: TR.init_state(k, cfg),
                                jax.random.PRNGKey(0))
    state_sh = train_state_shardings(state_spec, mesh)
    step, _ = TR.make_meta_step(cfg, S, mix_fn=mix_fn, jit=False)
    fn = jax.jit(step, in_shardings=(state_sh, agent_sh, rep),
                 out_shardings=(state_sh, rep))
    txt = fn.lower(state_spec, surf_batch_specs(cfg),
                   jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    parsed = hlo_cost.summarize(txt)
    return parsed["collective_bytes"], parsed["collectives"]


def seed_meta_step_collective_bytes(cfg, S_stack, mesh, mix_fn=None):
    """Per-META-STEP collective traffic of the SEED-BATCHED engine on a
    2-D ('seed', 'agent') mesh: lower ONE vmapped meta step (per-seed
    states/keys/S seed-sharded, the SHARED batch agent-sharded) and
    parse its post-SPMD HLO. ``mix_fn`` may be a seed-batched halo mixer
    (``topology.halo.make_seed_halo_mix``) — the vmap then carries its
    per-seed blocks with ``spmd_axis_name='seed'``, exactly like
    ``engine.seeds``; ``mix_fn=None`` lowers the dense per-lane
    ``S_i @ W`` baseline the halo path exists to beat. ``S_stack`` is
    the (n_seeds, n, n) static stand-in (a scheduled seed mixer binds
    its own blocks by the carried step and ignores it)."""
    from repro.engine.core import _meta_step_core
    from repro.sharding.surf_rules import agent_sharding, seed_sharding
    S_stack = jnp.asarray(S_stack, jnp.float32)
    n_seeds = int(S_stack.shape[0])
    seed_sh = seed_sharding(mesh, n_seeds)
    agent_sh = agent_sharding(mesh, cfg.n_agents)
    meta_step_s, _ = _meta_step_core(cfg, True, "relu", None, mix_fn)
    spmd = ("seed" if (mix_fn is not None and "seed" in mesh.axis_names)
            else None)
    if mix_fn is None:
        def step(states, batch, keys, S_stack):
            return jax.vmap(
                lambda S_i, st_i, k_i: meta_step_s(S_i, st_i, batch, k_i),
                in_axes=(0, 0, 0))(S_stack, states, keys)
    else:
        def step(states, batch, keys, S_stack):
            return jax.vmap(
                lambda S_i, st_i, k_i, blk_i: meta_step_s(
                    S_i, st_i, batch, k_i, blk_i),
                in_axes=(0, 0, 0, 0),
                spmd_axis_name=spmd)(S_stack, states, keys, mix_fn.blocks)
    keys_spec = jax.ShapeDtypeStruct((n_seeds, 2), jnp.uint32)
    states_spec = jax.eval_shape(
        lambda ks: jax.vmap(lambda k: TR.init_state(k, cfg))(ks), keys_spec)
    states_sh = jax.tree_util.tree_map(lambda _: seed_sh, states_spec)
    batch_spec = surf_batch_specs(cfg)
    batch_sh = jax.tree_util.tree_map(lambda _: agent_sh, batch_spec)
    # (n_seeds,) metric leaves stay seed-sharded like the engine outputs
    fn = jax.jit(step,
                 in_shardings=(states_sh, batch_sh, seed_sh, seed_sh),
                 out_shardings=(states_sh, seed_sh))
    txt = fn.lower(states_spec, batch_spec, keys_spec,
                   jax.ShapeDtypeStruct(tuple(S_stack.shape), jnp.float32)
                   ).compile().as_text()
    parsed = hlo_cost.summarize(txt)
    return parsed["collective_bytes"], parsed["collectives"]


def q_scan_collective_bytes(cfg, S, mesh, n_q, steps=4, eval_q=0,
                            q_sharded=True, naive_select=False):
    """Per-META-STEP collective traffic of the Q-SHARDED scan engine:
    lower the REAL engine body (``engine.scan._scan_run`` — the same
    select/meta-step/snapshot composition ``make_train_scan`` jits) with
    the train pool's Q axis sharded (``q_sharded=True``) or replicated
    (the baseline), plus an optionally Q-sharded in-scan snapshot pool
    (``eval_q`` > 0 snapshots every 2 steps), and parse the post-SPMD
    HLO.  Returns (collective bytes per meta-step, per-kind dict).

    With the owner-masked psum select, bytes are INDEPENDENT of ``n_q``
    (one dataset's bytes per step), where a naive dynamic index on the
    sharded pool would all-gather the whole pool (bytes ∝ Q).
    ``naive_select=True`` keeps the Q-sharded pool placement but drops
    back to the naive ``dynamic_index_in_dim`` select — the
    counterfactual that shows the growth the masked select removes.
    ``tests/test_qsharded.py::test_q_select_collective_bytes_flat_in_q``
    asserts both."""
    from repro.engine.core import _meta_step_core
    from repro.engine.scan import _scan_run
    from repro.engine.snapshots import make_snapshot_fn
    from repro.sharding.surf_rules import (make_q_select, q_select_axis,
                                           train_scan_shardings)
    steps = int(steps)
    batch_spec = surf_batch_specs(cfg)
    pool_spec = {k: jax.ShapeDtypeStruct((int(n_q),) + v.shape, v.dtype)
                 for k, v in batch_spec.items()}
    eval_every = 2 if eval_q else 0
    eval_spec = ({k: jax.ShapeDtypeStruct((int(eval_q),) + v.shape,
                                          v.dtype)
                  for k, v in batch_spec.items()} if eval_q else {})
    meta_step_s, _ = _meta_step_core(cfg, True, "relu", None, None, None)
    snap_fn = make_snapshot_fn(cfg, "relu", None) if eval_q else None
    select_fn = None
    if q_sharded and not naive_select:
        q_ax = q_select_axis(mesh, int(n_q))
        if q_ax is not None:
            select_fn = make_q_select(mesh, q_ax)

    def run(state, stacked, key, S, ev, S_ev):
        return _scan_run(meta_step_s, snap_fn, eval_every, cfg.n_layers,
                         state, stacked, key, steps, S, False, ev, S_ev,
                         select_fn=select_fn)

    in_sh, out_sh = train_scan_shardings(
        mesh, cfg.n_agents, stacked=pool_spec,
        eval_stacked=(eval_spec if eval_q else None),
        n_eval_q=(int(eval_q) if eval_q else None),
        q_sharded=q_sharded, n_q=int(n_q))
    fn = jax.jit(run, in_shardings=in_sh, out_shardings=out_sh)
    state_spec = jax.eval_shape(lambda k: TR.init_state(k, cfg),
                                jax.random.PRNGKey(0))
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    S_spec = jax.ShapeDtypeStruct((cfg.n_agents, cfg.n_agents),
                                  jnp.float32)
    txt = fn.lower(state_spec, pool_spec, key_spec, S_spec, eval_spec,
                   S_spec if eval_q else {}).compile().as_text()
    parsed = hlo_cost.summarize(txt)
    return parsed["collective_bytes"] / steps, parsed["collectives"]
