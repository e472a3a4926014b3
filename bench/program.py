"""What the benchmark takes from the program besides its entry points: the
program's configuration object for one of the benchmark's configurations."""
from __future__ import annotations

PROGRAM_KEYS = ("n_agents", "n_layers", "filter_taps", "feature_dim",
                "n_classes", "batch_per_agent", "train_per_agent",
                "test_per_agent", "eps", "lr_theta", "lr_lambda", "w0_mean",
                "w0_std", "topology", "degree")


def config(cfg):
    """``repro.configs.base.SURFConfig`` of a configuration file's sizes."""
    from repro.configs.base import SURFConfig
    return SURFConfig(**{k: cfg[k] for k in PROGRAM_KEYS})
