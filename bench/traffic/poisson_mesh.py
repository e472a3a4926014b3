"""Open-loop Poisson traffic of new federations at a fixed ``rate``, served
by all of a cell's chips (the ``serve_mesh`` job).

The arrivals and the federations are those of the ``poisson`` kind, taken
from it by import: the same seed gives the same requests. It is a kind of
its own so that one configuration served on one chip (``poisson``) and on
a host of four (``poisson_mesh``) is two (config, traffic) pairs of the
benchmark, each given once.
"""
from __future__ import annotations

import harness

_poisson = harness.load_module("traffic", "poisson")
due_times = _poisson.due_times
federations = _poisson.federations
graph_seed = _poisson.graph_seed
