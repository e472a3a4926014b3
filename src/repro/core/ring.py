"""Ring-topology graph filter as nearest-neighbour ``ppermute`` halo
exchanges instead of a dense S @ W (beyond-paper §Perf optimization).

Now a SPECIAL CASE of the general block-sparse halo mixer
(``repro.topology.halo``): the Metropolis matrix of a circulant
2h-regular ring is banded with offsets {0, ±1} at the shard level and
``hops`` needed boundary rows per direction, so ``make_halo_mix``
reproduces the original hand-written boundary-row exchange byte-for-
byte (O(hops·d) per mixing round vs the dense path's O(n·d/P)
all-gather) while also covering arbitrary banded / partition-local S.
This module keeps the ring-specific constructor and its stable
``("ring", ...)`` cache tag.

The shard-mapped plan is shared with every halo mixer
(``topology.halo._halo_filter_smapped``), so a ring mixer built with
``axis="agent"`` on a 2-D ``('seed', 'agent')``
``launch.mesh.make_surf_mesh`` permutes over the AGENT sub-axis and
composes under the seed-batched engine's ``spmd_axis_name='seed'`` vmap
exactly like ``make_seed_halo_mix``; the legacy ``axis="data"`` 1-D
meshes are the degenerate agent-only case.
"""
from __future__ import annotations


def make_ring_mix(mesh, axis: str, n: int, hops: int):
    """Returns the shard-mapped Horner graph filter ``mix_fn(W, h)`` for
    the 2·hops-regular circulant ring — ``make_halo_mix`` applied to
    ``metropolis_weights(ring_graph(n, hops))``.

    The returned function carries a hashable ``.tag`` attribute —
    ``("ring", axis, n, hops, mesh-fingerprint)`` — which the engine
    caches in ``repro.engine`` / ``core.surf`` fold into their keys so two
    ``make_ring_mix`` calls with identical geometry share one compiled
    engine (an untagged ``mix_fn`` disables caching instead)."""
    from repro.sharding.surf_rules import mesh_fingerprint
    from repro.topology.halo import make_halo_mix
    return make_halo_mix(mesh, axis, dense_equivalent(n, hops),
                         tag=("ring", axis, n, hops,
                              mesh_fingerprint(mesh)))


def dense_equivalent(n, hops):
    """The dense Metropolis mixing matrix the ring path must reproduce."""
    from repro.topology.families import metropolis_weights, ring_graph
    return metropolis_weights(ring_graph(n, hops))
