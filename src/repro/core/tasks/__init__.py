"""Pluggable inner FL problems for the one engine.

``Task`` is the interface (``base.py``); ``resolve_task(cfg, task)`` is
the single resolution point every consumer funnels through. Shipped
implementations: ``ClassificationTask`` (the paper's softmax head) and
``SparseRecoveryTask`` (federated LASSO). See ``engine/README.md``
§Tasks for the contract and how to add one.
"""
from repro.core.tasks.base import Task, resolve_task
from repro.core.tasks.classification import (ClassificationTask,
                                             classification_task)
from repro.core.tasks.sparse_recovery import (SparseRecoveryTask,
                                              soft_threshold,
                                              sparse_recovery_task,
                                              support_f1, signal_nmse)

__all__ = [
    "Task", "resolve_task",
    "ClassificationTask", "classification_task",
    "SparseRecoveryTask", "sparse_recovery_task",
    "soft_threshold", "support_f1", "signal_nmse",
]
