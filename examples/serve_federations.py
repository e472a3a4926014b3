"""Quickstart: serve NEW federations with a meta-trained amortized solver.

  PYTHONPATH=src python examples/serve_federations.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.data.synthetic import make_meta_dataset, sample_dataset
from repro.serve import FederationServer
from repro.utils.cache import use_compilation_cache


def main():
    state, _, _ = surf.train_surf(SMOKE, make_meta_dataset(SMOKE, 4),
                                  steps=30, log_every=0)
    server = FederationServer(SMOKE, state.theta)  # serves ANY cohort size
    server.warm([(SMOKE.n_agents, SMOKE.test_per_agent)])
    _, S_new = surf.make_problem(SMOKE, seed=99)   # an unseen federation
    fut = server.submit(S_new, sample_dataset(SMOKE, seed=99))
    server.drain()
    print(f"solved in one forward pass: final_acc="
          f"{float(fut.result()['final_acc']):.3f} "
          f"({fut.latency * 1e3:.1f} ms enqueue->complete)")


if __name__ == "__main__":
    use_compilation_cache()
    main()
