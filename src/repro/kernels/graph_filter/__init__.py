"""Fused K-hop graph filter  Y = Σ_k h_k S^k W  (eq. 6's hot op).

The argument order is ``graph_filter(S, W, h)`` — matching
``core.unroll.graph_filter`` and the engine's mixer protocol.

``make_pallas_mix()`` builds the engine-facing mixer
(``train_surf(mix="pallas")``); dispatch rules (backend-aware
``interpret``, ``block_d`` auto-pick, the ``impl="auto"`` jnp fallback)
live in ``ops.graph_filter``'s docstring.
"""
from repro.kernels.graph_filter.ops import graph_filter, make_pallas_mix
from repro.kernels.graph_filter.ref import graph_filter_ref

__all__ = ["graph_filter", "graph_filter_ref", "make_pallas_mix"]
