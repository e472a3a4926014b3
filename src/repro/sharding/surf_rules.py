"""NamedSharding rules for the SURF meta-training/evaluation engines.

AXIS ROLES, not axis names: every rule shards one of three roles —

  * the SEED role (``seed_sharding`` / ``seed_scan_shardings``): the
    leading per-seed axis of the seed-batched engine's stacks;
  * the AGENT role (``agent_sharding`` / ``stacked_*`` / Q rules): the
    agent dimension the halo/ring mixers ``ppermute`` over (the stacked
    eval pool's Q axis is data-parallel over the same devices, so it
    rides the agent role too, and so does the serving REQUEST axis);
  * the THETA role (``theta_shardings`` / ``place_theta``): the output
    columns of the serving perceptron M and of its bias d, for a θ too
    large for one device (``launch.mesh.serve_mesh`` decides the split).

``axis_for_role`` maps a role to the mesh axis that carries it: the
named ``'seed'``/``'agent'`` axes of a ``launch.mesh.make_surf_mesh``
2-D mesh, or the legacy ``'data'`` axis on the 1-D shim mesh
(``make_agent_mesh``, a ('data', 'model') mesh), where
BOTH roles degrade onto the single sharded axis and each engine uses
the one role it shards. Rules compose as pytree prefixes and default to
role resolution when no explicit axis is passed, so one rule set serves
1-D and 2-D meshes unchanged.

The scan engine (``repro.engine.make_train_scan``) is one jitted
computation, so the whole sharding story is three input specs:

  * ``TrainState`` (θ / λ / opt state) — REPLICATED. θ is the shared
    per-layer perceptron+filter-tap stack (Θ(d²), tiny next to the data)
    and every agent shard needs all of it, so replication is both correct
    and collective-free on the backward all-reduce path.
  * stacked meta-dataset pytree ``{k: (Q, n, ...)}`` — two regimes:
    - TRAIN (``stacked_agent_sharding``): the AGENT axis (dim 1) shards
      over 'data' so the per-step indexed batch arrives already
      agent-partitioned and the ring ``mix_fn`` halo exchange never sees
      a gather. Q stays replicated (one dataset is indexed per meta-step;
      sharding Q would turn every index into a cross-device fetch).
    - EVAL (``stacked_q_sharding``): the vmapped evaluator maps over Q,
      so the Q axis (dim 0) shards over 'data' — data-parallel
      evaluation over downstream datasets.
  * the agent axis of ``W`` / per-step batches (``agent_sharding``) —
    dim 0 over 'data', matching ``core.ring.make_ring_mix``'s
    ``in_specs=P('data')``.

Every rule degrades to replication when the dim doesn't divide the axis
(the same policy as ``sharding.rules``), so a 1-device CI mesh and an
indivisible Q both lower without error.

``mesh_fingerprint`` is the hashable mesh identity used by the engine
caches in ``repro.engine`` / ``core.surf`` — two jitted engines may only
share an executable when (axis names, axis sizes, device ids, platform)
all agree.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


ROLE_AXES = {"seed": "seed", "agent": "agent", "theta": "theta"}


def check_divides(count, shards, what, noun, fix):
    """The ONE actionable divisibility guard behind ``make_surf_mesh``,
    the halo planners and the seed-batched engine: an axis whose problem
    size doesn't divide its shard count fails UP FRONT naming the fix,
    instead of silently replicating (the ``_dim_spec`` fallback) or
    dying deep inside ``shard_map`` with a shape mismatch."""
    if shards <= 1 or count % shards == 0:
        return
    divisors = [d for d in range(1, count + 1) if count % d == 0]
    raise ValueError(
        f"{what}: {noun}={count} does not divide over {shards} shards — "
        f"{fix}; pick a shard count from the divisors of {count} "
        f"({divisors})")


def axis_for_role(mesh: Mesh, role: str):
    """Mesh axis carrying an axis ROLE ('seed' | 'agent'): the named axis
    of a ``make_surf_mesh`` 2-D mesh when present, else the legacy 'data'
    axis (1-D shim meshes name their single sharded axis 'data' whatever
    role it plays), else None (nothing to shard over — every rule
    replicates). The THETA role has no legacy axis: only a mesh that
    names 'theta' splits θ."""
    try:
        name = ROLE_AXES[role]
    except KeyError:
        raise ValueError(f"unknown axis role {role!r}; one of "
                         f"{sorted(ROLE_AXES)}")
    if name in mesh.axis_names:
        return name
    if role != "theta" and "data" in mesh.axis_names:
        return "data"
    return None


def mesh_fingerprint(mesh: Mesh | None):
    """Hashable identity of a mesh for engine-cache keys (None passes
    through so unsharded engines keep their old keys)."""
    if mesh is None:
        return None
    devs = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
    platform = np.asarray(mesh.devices).flat[0].platform
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            devs, platform)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    return int(mesh.shape[axis]) if axis in mesh.axis_names else 1


def _dim_spec(dim_size: int | None, mesh: Mesh, axis, position: int,
              ndim_hint: int | None = None) -> P:
    """P with ``axis`` at ``position`` when the dim divides the axis size,
    else fully replicated. ``dim_size=None`` skips the divisibility check
    (caller guarantees it, e.g. the ring path asserts n % nshards == 0)."""
    size = _axis_size(mesh, axis)
    if size <= 1:
        return P()
    if dim_size is not None and dim_size % size != 0:
        return P()
    spec = [None] * (position + 1)
    spec[position] = axis
    return P(*spec)


def agent_sharding(mesh: Mesh, n_agents: int | None = None,
                   axis=None) -> NamedSharding:
    """W / per-step batch leaves: agent axis (dim 0) over the AGENT-role
    axis (``axis`` overrides role resolution)."""
    axis = axis_for_role(mesh, "agent") if axis is None else axis
    return NamedSharding(mesh, _dim_spec(n_agents, mesh, axis, 0))


def stacked_agent_sharding(mesh: Mesh, n_agents: int | None = None,
                           axis=None) -> NamedSharding:
    """Stacked meta-dataset leaves (Q, n, ...): agent axis (dim 1) over
    the AGENT-role axis — the TRAIN-engine input spec (usable as a pytree
    prefix: trailing dims replicate)."""
    axis = axis_for_role(mesh, "agent") if axis is None else axis
    return NamedSharding(mesh, _dim_spec(n_agents, mesh, axis, 1))


def stacked_q_sharding(mesh: Mesh, n_q: int | None = None,
                       axis=None) -> NamedSharding:
    """Stacked meta-dataset leaves (Q, ...): Q axis (dim 0) over the
    AGENT-role axis (data-parallel evaluation rides the same devices the
    agent axis shards over) — the vmapped-EVAL input spec."""
    axis = axis_for_role(mesh, "agent") if axis is None else axis
    return NamedSharding(mesh, _dim_spec(n_q, mesh, axis, 0))


def stacked_q_tree(stacked, mesh: Mesh, n_q: int | None = None, axis=None):
    """Per-leaf Q shardings for a stacked dataset pytree: EVERY leaf of a
    ``stack_meta_datasets`` tree leads with the Q axis (the stacker adds
    the axis to every leaf, aux entries included), so one uniform
    ``stacked_q_sharding`` covers the tree. Degrades to replication as a
    unit when Q doesn't divide the axis."""
    q = stacked_q_sharding(mesh, n_q, axis)
    return jax.tree_util.tree_map(lambda _: q, stacked)


def q_select_axis(mesh: Mesh | None, n_q: int | None = None, axis=None):
    """The mesh axis a Q-SHARDED pool's per-step owner-masked select runs
    over, or None when the pool would replicate anyway (no mesh, axis size
    1, or indivisible Q) — the single gate both ``make_q_select`` and the
    Q-sharded placement rules consult, so the select and the shardings
    can never disagree."""
    if mesh is None:
        return None
    axis = axis_for_role(mesh, "agent") if axis is None else axis
    size = _axis_size(mesh, axis)
    if size <= 1 or n_q is None or n_q % size != 0:
        return None
    return axis


def make_q_select(mesh: Mesh, axis):
    """``select(stacked, t) -> batch`` for a Q-SHARDED meta-dataset pool:
    the per-meta-step dataset select that keeps collective bytes
    INDEPENDENT of Q.

    A plain ``dynamic_index_in_dim`` on a dim-0-sharded pool makes the
    SPMD partitioner all-gather the WHOLE pool every step (bytes ∝ Q;
    ``tests/test_qsharded.py::test_q_select_collective_bytes_flat_in_q``
    asserts the growth and this select's flat bytes). Instead each shard
    slices its LOCAL block at ``(t % n_q) % q_local``, masks the slice to
    zero unless it owns dataset ``t % n_q``, and a ``psum`` over the
    Q-carrying axis re-assembles exactly one dataset: one all-reduce of
    ONE dataset's bytes per step, whatever Q is. The masked sum adds
    exact zeros, so the selected batch is BIT-equal to the replicated
    index. ``n_q`` is derived from the local block (global dim 0 =
    local · shards), so one select serves every pool size."""
    n_shards = int(mesh.shape[axis])

    def select(stacked, t):
        def body(local, t):
            q_local = jax.tree_util.tree_leaves(local)[0].shape[0]
            q = t % (q_local * n_shards)
            own = (q // q_local) == jax.lax.axis_index(axis)

            def one(a):
                loc = jax.lax.dynamic_index_in_dim(a, q % q_local, 0,
                                                   keepdims=False)
                masked = jnp.where(own, loc, jnp.zeros_like(loc))
                return jax.lax.psum(masked, axis)
            return jax.tree_util.tree_map(one, local)

        return jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                             out_specs=P())(stacked, t)

    return select


def schedule_sharding(mesh: Mesh) -> NamedSharding:
    """The stacked (T, n, n) mixing-matrix schedule
    (``topology.schedule.TopologySchedule.S``): REPLICATED. Every agent
    shard reads the full S_t row block each meta-step and the stack is
    tiny next to the meta-dataset pool (40 MB at the paper's n=100,
    T=1000); sharding T would turn the per-step ``S[step % T]`` select
    into a cross-device fetch inside the scan body."""
    return replicated(mesh)


def train_state_shardings(state, mesh: Mesh):
    """Replicated sharding for every TrainState leaf (θ, λ, opt state,
    step). Accepts the state pytree or a ShapeDtypeStruct tree."""
    rep = replicated(mesh)
    return jax.tree_util.tree_map(lambda _: rep, state)


def stacked_shardings_tree(stacked, mesh: Mesh, n_agents: int,
                           axis=None):
    """Per-leaf shardings for a stacked meta-dataset pytree: leaves whose
    dim 1 IS the agent axis get ``stacked_agent_sharding``; anything else
    (auxiliary leaves without an agent axis, indivisible shapes)
    replicates. Leaf-aware on purpose — a pytree-prefix spec would reject
    nested aux entries riding along in the dataset dicts."""
    agent = stacked_agent_sharding(mesh, n_agents, axis)
    rep = replicated(mesh)

    def one(leaf):
        is_agent_leaf = leaf.ndim >= 2 and leaf.shape[1] == n_agents
        return agent if is_agent_leaf else rep
    return jax.tree_util.tree_map(one, stacked)


def stacked_sharded_flags(stacked, n_agents: int):
    """Hashable per-leaf summary of which stacked leaves carry the agent
    axis at dim 1 — combined with the treedef this keys compiled engines
    whose in_shardings differ only by dataset structure."""
    return tuple(bool(l.ndim >= 2 and l.shape[1] == n_agents)
                 for l in jax.tree_util.tree_leaves(stacked))


def train_scan_shardings(mesh: Mesh, n_agents: int | None = None,
                         axis=None, stacked=None, eval_stacked=None,
                         n_eval_q: int | None = None, q_sharded=False,
                         n_q: int | None = None):
    """(in_shardings, out_shardings) for the scan engine's
    ``run_s(state, stacked, key, S, eval_stacked, S_eval)`` dynamic
    arguments (``steps`` is static): state/key/S replicated, stacked
    agent-axis-sharded, the snapshot args (held-out eval pool + nominal
    S_eval — empty pytrees when ``eval_every`` is off) replicated;
    outputs (state, metrics, snaps) replicated. The S slot covers both a
    static (n, n) matrix and a stacked (T, n, n) ``TopologySchedule``
    array — both replicate (``schedule_sharding``). With ``stacked``
    given, the dataset entry is the leaf-aware tree from
    ``stacked_shardings_tree``; otherwise a pytree-prefix spec (only safe
    for flat Xtr/Ytr/Xte/Yte dicts whose every leaf has the agent axis at
    dim 1).

    Q-axis extensions (the two data-parallel pools):

      * ``eval_stacked``/``n_eval_q`` — the in-scan SNAPSHOT pool's slot
        gets ``stacked_q_tree`` (dim 0 over the AGENT-role axis): the
        dense vmapped snapshot eval partitions over Q with one small
        mean-reduce all-reduce per snapshot, whatever Q is. Degrades to
        replication when Q doesn't divide the axis.
      * ``q_sharded=True``/``n_q`` — the TRAIN pool itself shards its Q
        axis (dim 0) instead of the agent axis: the memory-capacity mode
        for the paper's 600-dataset pool (each device holds Q/P
        datasets). The per-step select MUST then be the owner-masked
        psum of ``make_q_select`` — a plain dynamic index would
        all-gather the whole pool every step. Gated by ``q_select_axis``
        so the placement and the select agree."""
    rep = replicated(mesh)
    if q_sharded and q_select_axis(mesh, n_q, axis) is not None:
        stacked_sh = (stacked_q_tree(stacked, mesh, n_q, axis)
                      if stacked is not None
                      else stacked_q_sharding(mesh, n_q, axis))
    elif stacked is None:
        stacked_sh = stacked_agent_sharding(mesh, n_agents, axis)
    else:
        stacked_sh = stacked_shardings_tree(stacked, mesh, n_agents, axis)
    if eval_stacked is not None:
        ev_sh = stacked_q_tree(eval_stacked, mesh, n_eval_q, axis)
    else:
        ev_sh = rep
    return (rep, stacked_sh, rep, rep, ev_sh, rep), (rep, rep, rep)


def seed_sharding(mesh: Mesh, n_seeds: int | None = None,
                  axis=None) -> NamedSharding:
    """Leading SEED axis (dim 0) over the SEED-role axis — the
    seed-batched train engine's per-seed spec (``engine.seeds``), usable
    as a pytree prefix: every per-seed leaf (TrainState stacks, key
    batch, S/schedule stacks, (n_seeds, steps) metrics) carries n_seeds
    at dim 0 and trailing dims replicate. Seeds are embarrassingly
    parallel, so this shards the whole training computation with zero
    hot-loop collectives."""
    axis = axis_for_role(mesh, "seed") if axis is None else axis
    return NamedSharding(mesh, _dim_spec(n_seeds, mesh, axis, 0))


def seed_scan_shardings(mesh: Mesh, n_seeds: int | None = None,
                        axis=None, n_agents: int | None = None,
                        stacked=None, eval_stacked=None,
                        n_eval_q: int | None = None, q_sharded=False,
                        n_q: int | None = None):
    """(in_shardings, out_shardings) for the seed-batched engine's
    ``run_s(states, stacked, keys, S_stack, eval_stacked, S_eval_stack)``
    dynamic arguments (``steps`` is static): per-seed stacks over the
    SEED-role axis; outputs (states, metrics, snaps) keep the seed axis
    sharded.

    The SHARED meta-training pool composes the AGENT role: on a 2-D
    ``('seed', 'agent')`` mesh its agent dim (dim 1, ``n_agents``) shards
    over 'agent' (replicated over 'seed') so the per-step indexed batch
    arrives already agent-partitioned for the halo ``ppermute`` exchange
    under the seed vmap — pass ``stacked`` for the leaf-aware tree
    (aux leaves without an agent axis replicate). On a 1-D mesh both
    roles resolve to the same axis, so the pool stays replicated (the
    pre-2-D behavior).

    Q-axis extensions mirror ``train_scan_shardings`` and apply ONLY on a
    2-D mesh (``agent_ax != seed_ax``): the snapshot pool
    (``eval_stacked``/``n_eval_q``) Q-shards dim 0 over 'agent' — the
    snapshot runs under the seed vmap, so the pool is replicated over
    'seed' and data-parallel over 'agent'; ``q_sharded``/``n_q`` Q-shards
    the shared TRAIN pool the same way (the engine pairs it with
    ``make_q_select``). On a 1-D mesh the seed lanes own the single
    sharded axis and both pools stay replicated — Q-sharding there would
    gather across seed lanes every step."""
    seed_ax = axis_for_role(mesh, "seed") if axis is None else axis
    agent_ax = axis_for_role(mesh, "agent")
    seed = seed_sharding(mesh, n_seeds, seed_ax)
    rep = replicated(mesh)
    two_d = (agent_ax is not None and agent_ax != seed_ax
             and _axis_size(mesh, agent_ax) > 1)
    if two_d and q_sharded and q_select_axis(mesh, n_q, agent_ax) is not None:
        stacked_sh = (stacked_q_tree(stacked, mesh, n_q, agent_ax)
                      if stacked is not None
                      else stacked_q_sharding(mesh, n_q, agent_ax))
    elif two_d:
        if stacked is not None:
            stacked_sh = stacked_shardings_tree(stacked, mesh, n_agents,
                                                agent_ax)
        else:
            stacked_sh = stacked_agent_sharding(mesh, n_agents, agent_ax)
    else:
        stacked_sh = rep
    if two_d and eval_stacked is not None:
        ev_sh = stacked_q_tree(eval_stacked, mesh, n_eval_q, agent_ax)
    else:
        ev_sh = rep
    return (seed, stacked_sh, seed, seed, ev_sh, seed), (seed, seed, seed)


# ------------------------------------------------------------------ theta
def theta_split(mesh: Mesh | None) -> int:
    """How many ways the THETA-role axis splits θ's columns (1: whole θ
    on every device)."""
    if mesh is None:
        return 1
    return _axis_size(mesh, axis_for_role(mesh, "theta"))


def padded_columns(d: int, split: int) -> int:
    """``d`` rounded up to a multiple of ``split``: the column count of a
    split θ (and of the served W), so that every device holds an equal
    block. The extra columns are exact zeros in M, d and W."""
    return -(-int(d) // int(split)) * int(split)


def theta_shardings(mesh: Mesh):
    """Per-leaf shardings of θ = {h (L, K+1), M (L, din, d), d (L, d)}:
    M's and d's output columns over the THETA-role axis, the taps h
    replicated. With no θ split every leaf replicates."""
    axis = axis_for_role(mesh, "theta")
    if _axis_size(mesh, axis) <= 1:
        rep = replicated(mesh)
        return {"h": rep, "M": rep, "d": rep}
    return {"h": replicated(mesh),
            "M": NamedSharding(mesh, P(None, None, axis)),
            "d": NamedSharding(mesh, P(None, axis))}


def _pad_theta_columns(theta, cols, xp=jnp):
    pad = cols - theta["M"].shape[-1]
    return {"h": theta["h"],
            "M": xp.pad(theta["M"], ((0, 0), (0, 0), (0, pad))),
            "d": xp.pad(theta["d"], ((0, 0), (0, pad)))}


def place_theta(theta, mesh: Mesh, d: int):
    """θ laid out for ``mesh`` (``theta_shardings``), its columns padded
    with zeros to ``padded_columns(d, theta_split(mesh))``. A θ already
    laid out passes through untouched; a host θ is padded on the host
    and each device receives only its block, so a split θ is never whole
    on one device."""
    split = theta_split(mesh)
    cols = padded_columns(d, split)
    shardings = theta_shardings(mesh)
    if theta["M"].shape[-1] != cols:
        if all(isinstance(a, np.ndarray) for a in theta.values()):
            theta = _pad_theta_columns(theta, cols, np)
        else:
            return jax.jit(_pad_theta_columns, static_argnums=1,
                           out_shardings=shardings)(theta, cols)
    return jax.device_put(theta, shardings)
