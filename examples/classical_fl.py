"""Classical (server-based) FL with U-DGD on a star graph (paper §5.2 +
Fig. 5 right): the server node only aggregates (graph-filter row), agents
do the local perceptron updates; K is constrained to 1.

  PYTHONPATH=src python examples/classical_fl.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SURFConfig
from repro.core import baselines as BL
from repro.core import surf, unroll as U
from repro.data import synthetic
from repro.utils.cache import use_compilation_cache


def main():
    cfg = SURFConfig(n_agents=30, n_layers=8, filter_taps=1, feature_dim=32,
                     n_classes=10, batch_per_agent=8, topology="star",
                     eps=0.1, lr_theta=1e-3)
    meta_train = synthetic.make_meta_dataset(cfg, 20, seed=0)
    state, _, S = surf.train_surf(cfg, meta_train, steps=300, log_every=0)
    test = synthetic.make_meta_dataset(cfg, 5, seed=7)

    res = surf.evaluate_surf(cfg, state, S, test)
    budget = cfg.n_layers
    print(f"U-DGD(SURF, star) @{budget:2d} rounds: acc={res['final_acc']:.3f}")

    for name, fn in BL.CLASSICAL.items():
        accs = []
        for d in test:
            batch = {k: jnp.asarray(v) for k, v in d.items()}
            W0 = U.sample_w0(jax.random.PRNGKey(0), cfg)
            out = fn(W0, batch, jax.random.PRNGKey(1), cfg, rounds=25,
                     lr=0.5, participate=10)
            accs.append(np.asarray(out["acc"]))
        acc = np.mean(accs, axis=0)
        print(f"{name:10s} @{budget:2d} rounds: acc={acc[budget-1]:.3f}   "
              f"@25 rounds: acc={acc[-1]:.3f}")


if __name__ == "__main__":
    use_compilation_cache()
    main()
