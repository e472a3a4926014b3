# The paper's primary contribution: SURF — stochastic unrolled federated
# learning. graph topologies / U-DGD unrolled layers / descending
# constraints / primal-dual meta-training / FL baselines.
#
# ``surf`` is NOT imported eagerly: it depends on the engine package,
# which itself imports ``repro.core.constraints``/``tasks``/``unroll`` —
# an eager import here would close that cycle when ``repro.engine`` is
# imported first. ``import repro.core.surf`` works via Python's
# on-demand submodule resolution, and attribute access
# (``repro.core.surf`` after ``import repro.core``) via the PEP 562
# module __getattr__ below.
from repro.core import baselines, constraints, unroll

__all__ = ["unroll", "constraints", "baselines", "surf"]

_LAZY = ("surf",)


def __getattr__(name):
    if name in _LAZY:
        import importlib
        module = importlib.import_module(f"repro.core.{name}")
        globals()[name] = module
        return module
    raise AttributeError(f"module 'repro.core' has no attribute {name!r}")
