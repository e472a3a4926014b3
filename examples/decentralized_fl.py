"""Decentralized FL head-to-head (paper Fig. 5 left/middle, compressed):
U-DGD trained via SURF vs DGD / DSGD / DFedAvgM on a 3-regular graph —
prints accuracy at matched communication-round budgets.

``--scenario`` meta-trains U-DGD under a TIME-VARYING topology
(``repro.topology.schedule``, one compiled schedule-aware scan engine):

  static        the paper's fixed graph (default),
  link-failure  every link drops i.i.d. w.p. 0.2 per meta-step,
  dropout       n/10 agents drop out (hold their value) per meta-step.

Evaluation always runs on the nominal static graph — the robustness
protocol of Hadou et al. (train perturbed, test nominal). The classical
baselines are topology-schedule-free by construction, so their columns
are unchanged; compare the U-DGD row across scenarios.

``--seeds N`` meta-trains N seeds in ONE compiled seed-batched engine
(each seed with its own init/topology/perturbation stream) and reports
the U-DGD row as mean±std over training seeds.

  PYTHONPATH=src python examples/decentralized_fl.py --scenario dropout
  PYTHONPATH=src python examples/decentralized_fl.py --seeds 4
"""
import argparse
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
from repro.topology import families as F
from repro.utils.cache import use_compilation_cache


def main(scenario="static", n_seeds=1):
    cfg = SURFConfig(n_agents=30, n_layers=8, filter_taps=2, feature_dim=32,
                     n_classes=10, batch_per_agent=8, topology="regular",
                     degree=3)
    meta_train = synthetic.make_meta_dataset(cfg, 60, seed=0)
    train_seeds = tuple(range(n_seeds)) if n_seeds > 1 else None
    state, _, S = surf.train_surf(cfg, meta_train, steps=800, log_every=0,
                                  engine="scan", scenario=scenario,
                                  seeds=train_seeds)
    from repro import engine as E
    states = ([E.state_for_seed(state, i) for i in range(n_seeds)]
              if train_seeds else [state])
    S_list = ([np.asarray(S[i]) for i in range(n_seeds)] if train_seeds
              else [np.asarray(S)])
    S = jnp.asarray(S_list[0])
    A = S_list[0] > 0
    np.fill_diagonal(A, False)
    print(f"scenario={scenario}: base graph (seed 0) SLEM="
          f"{F.second_eigenvalue(S_list[0]):.3f}, "
          f"algebraic connectivity={F.algebraic_connectivity(A):.3f}")
    test = synthetic.make_meta_dataset(cfg, 5, seed=42)

    # multi-seed evaluation layer: 4 eval seeds per trained model, one
    # compiled computation each (shapes identical -> one executable)
    finals = np.concatenate([
        np.asarray(surf.evaluate_surf(cfg, st, jnp.asarray(Si), test,
                                      seeds=(0, 1, 2, 3))["final_acc"])
        for st, Si in zip(states, S_list)])
    budget = cfg.n_layers * cfg.filter_taps
    tag = "U-DGD(SURF)" if scenario == "static" else \
        f"U-DGD({scenario})"
    print(f"{tag:12s} @{budget:3d} rounds: "
          f"acc={float(np.mean(finals)):.3f} "
          f"±{float(np.std(finals)):.3f} "
          f"({len(states)} train x 4 eval seeds)")

    lrs = {"dgd": 0.5, "dsgd": 0.2, "dfedavgm": 0.05}
    for name, fn in BL.DECENTRALIZED.items():
        accs_at_budget, accs_200 = [], []
        for d in test:
            batch = {k: jnp.asarray(v) for k, v in d.items()}
            W0 = U.sample_w0(jax.random.PRNGKey(0), cfg)
            out = fn(S, W0, batch, jax.random.PRNGKey(1), cfg, rounds=200,
                     lr=lrs[name])
            acc = np.asarray(out["acc"])
            accs_at_budget.append(acc[budget - 1])
            accs_200.append(acc[-1])
        print(f"{name:12s} @{budget:3d} rounds: "
              f"acc={np.mean(accs_at_budget):.3f}   "
              f"@200 rounds: acc={np.mean(accs_200):.3f}")
    print("\n(The paper's claim: U-DGD at ~20 rounds beats baselines at "
          "200 — check the first column against the last.)")


if __name__ == "__main__":
    use_compilation_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="static",
                    choices=("static", "link-failure", "dropout"),
                    help="topology schedule U-DGD meta-trains under "
                         "(evaluation stays on the nominal graph)")
    ap.add_argument("--seeds", type=int, default=1,
                    help="training seeds batched into one compiled "
                         "engine (default 1)")
    args = ap.parse_args()
    main(args.scenario, n_seeds=args.seeds)
