"""Block-sparse halo-exchange graph mixing: ``S @ W`` on an
agent-axis-sharded mesh for ARBITRARY mixing matrices — the
generalization of ``core.ring.make_ring_mix`` beyond circulant rings
(ROADMAP item "generalize the collective-efficient mix").

Decomposition: partition the n agents into ``nshards`` contiguous
blocks of ``nl = n/nshards`` rows. ``S`` then splits into shard-level
blocks ``S[a, b]`` and

    (S @ W)|_a  =  Σ_δ  S[a, (a+δ) mod nshards] @ W|_{(a+δ) mod nshards}

over shard offsets δ. Only offsets with at least one NONZERO block
anywhere incur communication — one ``ppermute`` per active offset —
and each ppermute carries only the UNION of source-block rows any
destination actually references (for a circulant ring of ``hops``
neighbours that is exactly ``hops`` boundary rows per direction, so the
ring filter of ``core.ring`` is the special case offsets = {0, ±1}).
Banded / partition-local matrices therefore move O(bandwidth · d)
bytes per mixing round instead of the dense path's all-gather of the
full W; a fully dense S degrades gracefully to all-pairs exchange
(same bytes as the all-gather, never worse than a failure).

Dense parity is exact by construction — every nonzero of S lands in
exactly one offset block — and unit-tested to ≤1e-5 against
``unroll.graph_filter`` for ring, regular and small-world graphs on 8
simulated devices (``tests/test_sharded_engine.py``).

The returned ``mix_fn(W, h)`` applies the K-tap Horner filter
Σ_k h_k S^k W with one halo exchange per mixing round and carries a
hashable ``.tag`` — ``("halo", axis, n, nshards, content-hash-of-S,
mesh-fingerprint)`` — for the compiled-engine caches in
``repro.engine`` / ``core.surf`` (S's VALUES are baked into the
closure, so the tag must identify them: a content hash, not a family
name).

Time-varying schedules (``topology.schedule``) whose halo plan is
TIME-CONSTANT — the offset/row structure of the UNION support
``∪_t supp(S_t)`` — ride the same exchange via
``make_scheduled_halo_mix``: the per-offset coefficient blocks are
stacked over T, threaded through the jitted scan as device arrays, and
the engine binds step t's blocks with ``mix.at_step(state.step)``.
Link-failure / Markov / dropout schedules never ADD edges to their base
graph, so their union is the base topology and a banded base keeps its
ppermute collective-bytes savings under time variation; only schedules
whose union densifies (e.g. a ring→random anneal) should fall back to
the dense ``S_t @ W`` path.

On a 2-D ``('seed', 'agent')`` mesh (``launch.mesh.make_surf_mesh``)
the same exchange composes with SEED parallelism: ``make_seed_halo_mix``
stacks per-seed coefficient blocks under one union plan and the
seed-batched engine runs the shard-mapped filter under
``jax.vmap(..., spmd_axis_name='seed')`` — every seed row of the mesh
ppermutes only its own lanes' boundary rows over its agent sub-axis.
All three mixers share one shard-mapped filter body
(``_halo_filter_smapped``); they differ only in how the coefficient
blocks are bound (baked / by carried step / by seed lane + step).
"""
from __future__ import annotations

import hashlib
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _check_divisible(n, nshards, what="halo plan"):
    """Every halo planner fails an indivisible agent axis HERE with the
    shared actionable message, not deep inside ``shard_map`` with a
    shape mismatch."""
    from repro.sharding.surf_rules import check_divides
    check_divides(n, nshards, what, "n",
                  f"the halo exchange gives every shard an equal "
                  f"n/{nshards} row block of W; build the mesh via "
                  f"launch.mesh.make_surf_mesh(seed_shards, agent_shards, "
                  f"n_agents={n})")


RESIDENTS = ("dense", "pallas")


def _resident_matmul(resident):
    """The per-hop RESIDENT block product ``S0_loc @ Y`` of the halo
    filter: a plain einsum (``resident="dense"``) or the Pallas
    graph-filter kernel called as its 1-tap special case
    ``h=[0, 1] → 0·Y + 1·S0 Y`` (``resident="pallas"``) — S0 stays
    VMEM-resident and the product runs through the kernel's custom VJP,
    so meta-gradients flow the same fused path the dense ``mix="pallas"``
    variant uses. Boundary rows keep the ``ppermute`` exchange either
    way; only the communication-free on-shard block changes engines."""
    if resident not in RESIDENTS:
        raise ValueError(f"resident must be one of {RESIDENTS}, got "
                         f"{resident!r}")
    if resident == "dense":
        return lambda S0, Y: S0 @ Y
    from repro.kernels.graph_filter import graph_filter
    one_hop = jnp.array([0.0, 1.0], jnp.float32)
    return lambda S0, Y: graph_filter(S0, Y, one_hop, impl="pallas")


def _row_take(rows):
    """``Y -> Y[rows]`` for a static sorted row set, as static slices of
    its consecutive runs. A gather here would transpose to a
    ``scatter_add`` whose index operand misses the 'seed' axis that the
    seed vmap's ``spmd_axis_name`` adds to Y, which shard_map's
    varying-axis check refuses; slices transpose to pads."""
    rows = np.asarray(rows)
    runs = np.split(rows, np.nonzero(np.diff(rows) != 1)[0] + 1)
    bounds = [(int(r[0]), int(r[-1]) + 1) for r in runs]
    return lambda Y: jnp.concatenate(
        [jax.lax.slice_in_dim(Y, a, b, axis=0) for a, b in bounds], axis=0)


def _halo_filter_smapped(mesh, axis, row_sets, perms, resident="dense"):
    """The shared shard-mapped K-tap Horner graph filter
    ``(W_loc, h, S0_loc, Sd_locs) -> Y_loc`` over the AGENT sub-axis
    ``axis``: one ``ppermute`` per active shard offset, carrying only
    that offset's union rows. Every halo mixer (static ``make_halo_mix``,
    ``ScheduledHaloMix``, ``SeedHaloMix``) applies the same traced
    exchange and differs only in how it binds the coefficient blocks.
    Because the in/out specs mention ONLY ``axis``, the mapped filter
    composes under an outer seed vmap (``jax.vmap(...,
    spmd_axis_name='seed')`` on a 2-D ('seed', 'agent') mesh): the
    batching rule inserts 'seed' at the lane dim and each seed row of
    the mesh ppermutes its own lanes' boundary rows over its agent
    sub-axis. ``resident`` selects the on-shard block engine
    (``_resident_matmul``)."""
    res_mm = _resident_matmul(resident)
    takes = [_row_take(rows) for rows in row_sets]

    def apply_S(Y, S0_loc, Sd_locs):
        # Y (nl, d) local block; S0_loc (1, nl, nl); Sd_locs[i] (1, nl, r_i)
        out = res_mm(S0_loc[0], Y)
        for take, perm, Sd in zip(takes, perms, Sd_locs):
            recv = jax.lax.ppermute(take(Y), axis, perm)
            out = out + Sd[0] @ recv
        return out

    def filter_local(W_loc, h, S0_loc, Sd_locs):
        K = h.shape[0] - 1
        Y = h[K] * W_loc
        for k in range(K - 1, -1, -1):
            Y = apply_S(Y, S0_loc, Sd_locs) + h[k] * W_loc
        return Y

    # jax has no varying-axis rule for pallas_call inside shard_map; the
    # specs here are fully explicit (every input/output names its axis),
    # so disabling the redundant check for the pallas resident is safe —
    # the dense resident keeps the default checking.
    return jax.shard_map(
        filter_local, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), tuple(P(axis) for _ in row_sets)),
        out_specs=P(axis), check_vma=(resident == "dense"))


def _offset_perms(plans, nshards):
    return [[(j, (j - delta) % nshards) for j in range(nshards)]
            for delta, _, _ in plans]


def halo_plan(S, nshards):
    """The static exchange plan for ``S`` on ``nshards`` shards.

    Returns ``(S0, plans)``: ``S0`` (nshards, nl, nl) is the
    block-diagonal (offset-0, communication-free) part; ``plans`` is a
    list of ``(delta, rows, Sd)`` per active nonzero offset δ ≠ 0 with
    ``rows`` the union of source-block row indices any shard needs
    (what the δ-ppermute carries) and ``Sd`` (nshards, nl, len(rows))
    the per-shard coefficient blocks restricted to those rows."""
    S = np.asarray(S, np.float32)
    n = S.shape[0]
    if S.ndim != 2 or S.shape[1] != n:
        raise ValueError(f"halo plan: S must be (n, n), got shape "
                         f"{tuple(S.shape)}")
    _check_divisible(n, nshards)
    nl = n // nshards
    blocks = S.reshape(nshards, nl, nshards, nl).transpose(0, 2, 1, 3)
    a = np.arange(nshards)
    S0 = blocks[a, a]                               # (nshards, nl, nl)
    plans = []
    for delta in range(1, nshards):
        blk = blocks[a, (a + delta) % nshards]      # (nshards, nl, nl)
        if not blk.any():
            continue
        rows = np.nonzero(blk.any(axis=(0, 1)))[0]  # union of needed rows
        plans.append((delta, rows, np.ascontiguousarray(blk[:, :, rows])))
    return S0, plans


def halo_exchange_rows(plans):
    """Total rows moved per shard per mixing round — the static
    collective-cost model of a plan (the dense path all-gathers
    (nshards−1)·nl rows instead)."""
    return sum(len(rows) for _, rows, _ in plans)


def make_halo_mix(mesh, axis: str, S, *, tag=None, resident="dense"):
    """Shard-mapped block-sparse Horner graph filter ``mix_fn(W, h)``
    reproducing ``unroll.graph_filter(S, W, h)`` with the agent axis of
    ``W`` sharded over mesh axis ``axis``.

    Works for ANY (n, n) mixing matrix with n divisible by the shard
    count — including nshards=1, where it reduces to the local dense
    matmul. ``tag`` overrides the content-hash cache tag (e.g.
    ``core.ring`` re-tags its circulant special case).
    ``resident="pallas"`` runs each shard's on-shard block product
    through the Pallas graph-filter kernel (``_resident_matmul``) —
    the ``mix="halo-pallas"`` variant of ``core.surf.train_surf`` —
    and the cache tag keys apart as ``"halo-pallas"``."""
    S = np.asarray(S, np.float32)
    n = S.shape[0]
    nshards = int(mesh.shape[axis])
    S0, plans = halo_plan(S, nshards)
    S0_dev = jnp.asarray(S0)
    Sd_devs = tuple(jnp.asarray(Sd) for _, _, Sd in plans)
    smapped = _halo_filter_smapped(mesh, axis,
                                   [rows for _, rows, _ in plans],
                                   _offset_perms(plans, nshards),
                                   resident=resident)

    def mix_fn(W, h):
        return smapped(W, h, S0_dev, Sd_devs)

    if tag is None:
        from repro.sharding.surf_rules import mesh_fingerprint
        digest = hashlib.sha256(S.tobytes()).hexdigest()[:16]
        kind = "halo" if resident == "dense" else "halo-pallas"
        tag = (kind, axis, n, nshards, digest, mesh_fingerprint(mesh))
    mix_fn.tag = tag
    mix_fn.plan = (S0, plans)
    return mix_fn


def scheduled_halo_plan(S_stack, nshards):
    """Time-constant exchange plan for a stacked (T, n, n) schedule: the
    offset/row structure of the UNION support ``∪_t supp(S_t)``, with
    per-step coefficient blocks restricted to the union's row sets.

    Returns ``(S0_t, plans)``: ``S0_t`` (T, nshards, nl, nl) is the
    block-diagonal part per step; ``plans`` is a list of
    ``(delta, rows, Sd_t)`` per offset active ANYWHERE in the schedule,
    ``Sd_t`` (T, nshards, nl, len(rows)). Every ppermute carries the
    union rows at every step — a step whose S_t doesn't reference some
    row just multiplies it by zero — so the plan (and the traced
    computation) is identical across t."""
    S_stack = np.asarray(S_stack, np.float32)
    if S_stack.ndim != 3 or S_stack.shape[1] != S_stack.shape[2]:
        raise ValueError(f"scheduled halo plan: S_stack must be (T, n, n), "
                         f"got shape {tuple(S_stack.shape)}")
    T, n, _ = S_stack.shape
    _check_divisible(n, nshards, "scheduled halo plan")
    nl = n // nshards
    union = (S_stack != 0.0).any(axis=0).astype(np.float32)
    _, plans_u = halo_plan(union, nshards)
    blocks = (S_stack.reshape(T, nshards, nl, nshards, nl)
              .transpose(0, 1, 3, 2, 4))        # (T, a, b, nl, nl)
    a = np.arange(nshards)
    S0_t = blocks[:, a, a]                      # (T, nshards, nl, nl)
    plans = []
    for delta, rows, _ in plans_u:
        blk = blocks[:, a, (a + delta) % nshards]   # (T, nshards, nl, nl)
        plans.append((delta, rows, np.ascontiguousarray(blk[:, :, :, rows])))
    return S0_t, plans


class ScheduledHaloMix:
    """Halo mixer for a time-constant-plan schedule: ``at_step(t)``
    returns the step-``t % T`` graph filter ``mix_fn(W, h)`` by
    dynamically indexing the stacked per-offset blocks — usable inside a
    jitted scan with a TRACED ``t`` (the engine passes the carried
    ``state.step``, so checkpoint-restored runs resume the exact mixing
    stream). ``scheduled``/``steps``/``tag`` are the engine protocol:
    ``repro.engine`` re-binds the mixer every meta-step instead of
    rejecting it the way it rejects static mixers under a schedule."""

    scheduled = True

    def __init__(self, mesh, axis, S_stack, *, tag=None, resident="dense"):
        S_stack = np.asarray(S_stack, np.float32)
        T, n, _ = S_stack.shape
        nshards = int(mesh.shape[axis])
        S0_t, plans = scheduled_halo_plan(S_stack, nshards)
        self._S0 = jnp.asarray(S0_t)            # (T, nshards, nl, nl)
        self._Sd = tuple(jnp.asarray(Sd) for _, _, Sd in plans)
        self._smapped = _halo_filter_smapped(mesh, axis,
                                             [rows for _, rows, _ in plans],
                                             _offset_perms(plans, nshards),
                                             resident=resident)
        self.steps = T
        self.plan = (S0_t, plans)
        # content identity of the schedule the blocks were built from —
        # the engine refuses a (schedule, mixer) pair whose digests
        # disagree (same guard as rejecting static mixers under a
        # schedule, but for the right-shape-wrong-values case)
        self.schedule_digest = hashlib.sha256(
            S_stack.tobytes()).hexdigest()[:16]
        if tag is None:
            from repro.sharding.surf_rules import mesh_fingerprint
            kind = ("halo-sched" if resident == "dense"
                    else "halo-sched-pallas")
            tag = (kind, axis, n, T, nshards,
                   self.schedule_digest, mesh_fingerprint(mesh))
        self.tag = tag

    def at_step(self, t):
        """The graph filter for meta-step ``t`` (cycling mod T) — ``t``
        may be a traced scalar (the carried ``state.step``)."""
        ti = t % self.steps
        S0 = jax.lax.dynamic_index_in_dim(self._S0, ti, 0, keepdims=False)
        Sds = tuple(jax.lax.dynamic_index_in_dim(Sd, ti, 0, keepdims=False)
                    for Sd in self._Sd)
        return lambda W, h: self._smapped(W, h, S0, Sds)


def make_scheduled_halo_mix(mesh, axis: str, schedule, *, tag=None,
                            resident="dense"):
    """Build the time-constant-plan halo mixer for a
    ``topology.schedule.TopologySchedule`` (or a raw (T, n, n) stack):
    pass it as ``mix_fn`` TOGETHER with the schedule to
    ``engine.make_train_scan`` and time-varying training keeps the
    ppermute exchange instead of the dense ``S_t @ W`` fallback.
    ``resident="pallas"`` fuses each step's on-shard block into the
    Pallas kernel (see ``_resident_matmul``)."""
    S_stack = schedule.S if hasattr(schedule, "S") else schedule
    return ScheduledHaloMix(mesh, axis, S_stack, tag=tag, resident=resident)


class SeedHaloMix:
    """Per-SEED halo mixer for the seed-batched engine on a 2-D
    ``('seed', 'agent')`` mesh: one seed- (and, for schedule stacks,
    time-) constant exchange plan over the UNION support across every
    seed's mixing matrices, with per-seed coefficient blocks stacked at
    dim 0.

    Engine protocol (``seed_batched = True``): ``repro.engine.seeds``
    vmaps its meta step over ``(S_i, state_i, key_i, blocks_i)`` with
    ``spmd_axis_name='seed'`` and calls ``bind(blocks_i, state.step)``
    inside each lane — the bound filter runs the shared shard-mapped
    exchange (``_halo_filter_smapped``) whose specs mention only the
    AGENT axis, so the per-offset ``ppermute``s execute over each seed
    row's agent sub-axis while the lanes stay sharded over 'seed'.

    ``S_stack``: (n_seeds, n, n) static per-seed matrices, or
    (n_seeds, T, n, n) per-seed schedule stacks (``scheduled = True``;
    ``bind`` dynamic-indexes the lane's T axis by the carried step, so
    checkpoint-restored runs resume the exact per-seed mixing streams).
    Seeds of a scenario share a base graph and perturbations never ADD
    edges, so the union across seeds/steps keeps a banded base's
    ppermute savings — same argument as the scheduled mixer's union.
    """

    seed_batched = True

    def __init__(self, mesh, axis, S_stack, *, tag=None, resident="dense"):
        # remember WHICH array object the blocks were built from: the
        # engine's content-digest guard short-circuits on identity, so
        # the common build-mixer-then-train path (train_surf(mix="halo"))
        # never re-transfers and re-hashes the full stack per call
        try:
            self._src_ref = weakref.ref(S_stack)
        except TypeError:
            self._src_ref = None
        S_stack = np.asarray(S_stack, np.float32)
        if S_stack.ndim == 3:
            scheduled = False
            n_seeds, n, n2 = S_stack.shape
        elif S_stack.ndim == 4:
            scheduled = True
            n_seeds, T, n, n2 = S_stack.shape
        else:
            raise ValueError(
                "SeedHaloMix: S_stack must be (n_seeds, n, n) or "
                f"(n_seeds, T, n, n), got shape {tuple(S_stack.shape)}")
        if n2 != n:
            raise ValueError(f"SeedHaloMix: mixing matrices must be "
                             f"square, got {(n, n2)}")
        nshards = int(mesh.shape[axis])
        flat = S_stack.reshape(-1, n, n)
        union = (flat != 0.0).any(axis=0).astype(np.float32)
        _, plans_u = halo_plan(union, nshards)
        nl = n // nshards
        blocks = (flat.reshape(-1, nshards, nl, nshards, nl)
                  .transpose(0, 1, 3, 2, 4))    # (B, a, b, nl, nl)
        a = np.arange(nshards)
        lead = (n_seeds, T) if scheduled else (n_seeds,)
        S0 = blocks[:, a, a]                    # (B, nshards, nl, nl)
        plans = []
        for delta, rows, _ in plans_u:
            blk = blocks[:, a, (a + delta) % nshards]
            plans.append((delta, rows,
                          np.ascontiguousarray(blk[:, :, :, rows])))
        self._smapped = _halo_filter_smapped(
            mesh, axis, [rows for _, rows, _ in plans],
            _offset_perms(plans, nshards), resident=resident)
        S0 = S0.reshape(lead + S0.shape[1:])
        plans = [(d, rows, Sd.reshape(lead + Sd.shape[1:]))
                 for d, rows, Sd in plans]
        # the engine vmaps ``blocks`` with in_axes=0 — each lane binds
        # its own (T,)?(nshards, nl, ·) coefficient blocks
        self.blocks = (jnp.asarray(S0),
                       tuple(jnp.asarray(Sd) for _, _, Sd in plans))
        self.plan = (S0, plans)
        self.scheduled = scheduled
        self.steps = T if scheduled else None
        self.n_seeds = n_seeds
        self.stack_digest = hashlib.sha256(
            S_stack.tobytes()).hexdigest()[:16]
        if tag is None:
            from repro.sharding.surf_rules import mesh_fingerprint
            kind = ("halo-seeds" if resident == "dense"
                    else "halo-seeds-pallas")
            tag = (kind, axis, n, n_seeds,
                   T if scheduled else 0, nshards, self.stack_digest,
                   mesh_fingerprint(mesh))
        self.tag = tag

    def bind(self, lane_blocks, t):
        """The graph filter for ONE seed lane: ``lane_blocks`` is the
        engine-vmap's dim-0 slice of ``self.blocks``; scheduled stacks
        additionally select step ``t % T`` (``t`` may be the traced
        carried ``state.step``)."""
        S0, Sds = lane_blocks
        if self.scheduled:
            ti = t % self.steps
            S0 = jax.lax.dynamic_index_in_dim(S0, ti, 0, keepdims=False)
            Sds = tuple(jax.lax.dynamic_index_in_dim(Sd, ti, 0,
                                                     keepdims=False)
                        for Sd in Sds)
        return lambda W, h: self._smapped(W, h, S0, Sds)


def make_seed_halo_mix(mesh, axis: str, S_stack, *, tag=None,
                       resident="dense"):
    """Build the per-seed halo mixer for ``train_surf(seeds=...)`` /
    ``engine.seeds.make_seed_train_scan`` on a 2-D ('seed', 'agent')
    mesh. ``S_stack``: the per-seed (n_seeds, n, n) static stack or
    (n_seeds, T, n, n) schedule stack the engine trains with (also
    accepts a list of per-seed ``TopologySchedule``s).
    ``resident="pallas"`` fuses each lane's on-shard block into the
    Pallas kernel (see ``_resident_matmul``)."""
    if isinstance(S_stack, (list, tuple)):
        S_stack = np.stack([np.asarray(s.S if hasattr(s, "S") else s,
                                       np.float32) for s in S_stack])
    return SeedHaloMix(mesh, axis, S_stack, tag=tag, resident=resident)
