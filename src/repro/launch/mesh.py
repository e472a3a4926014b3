"""The SURF training and serving meshes. FUNCTIONS, not module
constants — importing this module never touches jax device state.

``make_surf_mesh(seed_shards, agent_shards)`` is the ONE axis system the
SURF engines consume: a named ``('seed', 'agent')`` 2-D mesh whose axes
carry the two roles every engine shards — the embarrassingly-parallel
SEED axis of the seed-batched trainer and the AGENT axis the halo/ring
``ppermute`` mixers permute over (``sharding.surf_rules.axis_for_role``
maps role → axis name; the legacy 1-D ``make_agent_mesh`` and its
``'data'`` axis are the degenerate agent-only case, kept as a shim).

CI runs the sharded path on simulated host devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``make test-sharded`` lane) makes ``host_device_count()`` report 8 and
``make_surf_mesh(2, 4)`` build a real (seed=2, agent=4) mesh whose
``ppermute`` collectives execute with nshards > 1.

``serve_mesh(devices, cfg)`` is the serving layout: a named
``('agent', 'theta')`` mesh whose 'agent' axis carries REQUEST slots and
whose 'theta' axis splits θ's perceptron by columns. ``serve_layout``
picks the smallest θ split whose share fits a device, from the shapes and
the device's ``bytes_limit`` alone, and gives the other devices to
requests.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# the share of a device's bytes_limit that θ's block may take in serving;
# the rest holds the working set (request slots, one layer's activations
# and its bfloat16 copy of M's block, the outputs)
THETA_HBM_SHARE = 0.8


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with AUTO axes: every rule in
    ``sharding.surf_rules`` and every ``shard_map`` here places arrays by
    explicit ``NamedSharding``s and lets the partitioner propagate the
    rest, which is the Auto contract. ``jax.make_mesh`` now defaults to
    Explicit axes, whose sharding-in-types rejects e.g. a vmap over an
    agent-sharded W beside replicated batches."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def host_device_count() -> int:
    """Number of addressable devices on this host — 1 on a plain-CPU CI
    run, N under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``,
    the real chip count on hardware."""
    return len(jax.devices())


def make_surf_mesh(seed_shards: int = 1, agent_shards: int = 1, *,
                   n_seeds: int | None = None, n_agents: int | None = None):
    """The SURF axis system: a named ``('seed', 'agent')`` 2-D mesh.

    ``seed_shards`` devices on the 'seed' axis (the seed-batched engine
    shards per-seed TrainState/key/S stacks over it — embarrassingly
    parallel, zero hot-loop collectives) × ``agent_shards`` on the
    'agent' axis (the halo/ring mixers ``ppermute`` over it). Either
    degenerates cleanly: ``make_surf_mesh(1, P)`` is an agent-only mesh
    for single-seed sharded training, ``make_surf_mesh(P, 1)`` a
    seed-only mesh for dense multi-seed runs.

    ``n_seeds`` / ``n_agents``: optional problem sizes to validate UP
    FRONT — an indivisible axis would otherwise silently replicate (the
    sharding-rule fallback) or fail deep inside ``shard_map``; here it
    raises an actionable error instead."""
    from repro.sharding.surf_rules import check_divides
    seed_shards, agent_shards = int(seed_shards), int(agent_shards)
    if seed_shards < 1 or agent_shards < 1:
        raise ValueError(f"make_surf_mesh: shard counts must be >= 1, got "
                         f"seed_shards={seed_shards} "
                         f"agent_shards={agent_shards}")
    if n_seeds is not None:
        check_divides(n_seeds, seed_shards, "make_surf_mesh", "n_seeds",
                      "the seed-batched engine gives every shard an equal "
                      "block of seed lanes; pass a seed batch whose "
                      f"length is a multiple of seed_shards={seed_shards}")
    if n_agents is not None:
        check_divides(n_agents, agent_shards, "make_surf_mesh", "n_agents",
                      "the halo exchange gives every shard an equal row "
                      f"block of W; lower agent_shards={agent_shards}")
    need = seed_shards * agent_shards
    if need > host_device_count():
        raise ValueError(
            f"make_surf_mesh: ({seed_shards}, {agent_shards}) needs "
            f"{need} devices but only {host_device_count()} are visible "
            f"(CI: set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need})")
    return _make_mesh((seed_shards, agent_shards), ("seed", "agent"))


def make_agent_mesh(n_shards: int | None = None):
    """DEGENERATE-CASE SHIM: the legacy 1-D agent-axis mesh — ``n_shards``
    devices on 'data' (the axis ``core.ring.make_ring_mix`` historically
    permutes over), a trivial 'model' axis so the same P('data', ...)
    specs work on every mesh in this repo. Defaults to all addressable
    devices. New code should build ``make_surf_mesh(1, n_shards)`` and
    let ``sharding.surf_rules.axis_for_role`` resolve the axis name; this
    shim keeps the 'data' spelling for existing call sites."""
    n = host_device_count() if n_shards is None else int(n_shards)
    if n > host_device_count():
        raise ValueError(
            f"make_agent_mesh: {n} shards requested but only "
            f"{host_device_count()} devices visible (CI: set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")
    return _make_mesh((n, 1), ("data", "model"))


def serve_theta_bytes(cfg, task=None) -> int:
    """Bytes of θ = {h (L, K+1), M (L, din, d), d (L, d)} in float32,
    from the configuration's shapes alone."""
    from repro.core.tasks import resolve_task
    from repro.core.unroll import perceptron_in_dim
    task = resolve_task(cfg, task)
    d, din = task.dim, perceptron_in_dim(cfg, task)
    return 4 * cfg.n_layers * (din * d + d + cfg.filter_taps + 1)


def serve_layout(theta_bytes: int, n_devices: int, bytes_limit=None):
    """(request_shards, theta_split) for serving a θ of ``theta_bytes``
    on ``n_devices``: the smallest split, among the divisors of
    ``n_devices``, whose share of θ takes at most ``THETA_HBM_SHARE`` of
    a device's ``bytes_limit`` (None: no limit known, θ whole); the other
    devices serve requests side by side."""
    for split in range(1, n_devices + 1):
        if n_devices % split:
            continue
        if (bytes_limit is None
                or theta_bytes / split <= THETA_HBM_SHARE * bytes_limit):
            return n_devices // split, split
    raise ValueError(
        f"serve_layout: θ of {theta_bytes / 1e9:.2f} GB does not fit "
        f"{n_devices} devices of {bytes_limit / 1e9:.2f} GB each (θ may "
        f"take {THETA_HBM_SHARE:.0%} of a device)")


def serve_mesh(devices, cfg, task=None):
    """The serving mesh over ``devices``: ``('agent', 'theta')`` shaped by
    ``serve_layout`` from θ's bytes and the first device's
    ``memory_stats()['bytes_limit']``. One device gives None: the
    single-device server, with no mesh at all."""
    devices = list(devices)
    if len(devices) == 1:
        return None
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    shape = serve_layout(serve_theta_bytes(cfg, task), len(devices), limit)
    return jax.make_mesh(shape, ("agent", "theta"), devices=devices,
                         axis_types=(AxisType.Auto,) * 2)
