"""Serving telemetry: throughput, latency percentiles, bucket occupancy
and pad waste.

``ServeMetrics`` accumulates one record per completed request and one
per solver tick; ``summary()`` condenses them into these numbers
(``tests/test_serve.py::test_metrics_summary_fields`` checks them):

  * ``federations_per_sec`` — completed requests over wall time, from
    the earliest submit of a completed request to the last completion;
  * ``latency_p50_ms`` / ``latency_p99_ms`` — from ``submit``'s entry to
    completion, so featurization and queueing count, exactly what a
    caller observes;
  * ``occupancy`` — admitted requests over offered batch slots (low
    occupancy = the stream is too fragmented for ``max_batch``);
  * ``pad_waste`` — 1 − useful/padded compute cells, where a cell is
    one (agent × test-row) unit; waste comes from bucket rounding AND
    empty batch slots;
  * ``bucket_cache`` — hit/miss/insert/eviction counts of the server's
    bucket-executable LRU (``repro.cache_stats()`` format), so cache
    churn and pad waste are diagnosable together;
  * adaptive-depth telemetry (``depth="adaptive"`` servers only) —
    ``depth_hist`` counts realized per-request depths,
    ``request_flops_saved`` = 1 − Σdepth/(N·L) is the per-request
    layer-work fraction the early exit skipped, and
    ``batch_flops_saved`` = 1 − Σtrip/(ticks·L) is what the BATCH
    actually saved (a tick's while-loop runs to its slowest request, so
    batch savings lag request savings under mixed difficulty).
"""
from __future__ import annotations

import time

import numpy as np


class ServeMetrics:
    def __init__(self, cache=None):
        # the server's bucket-executable BoundedLRU; its live stats()
        # ride along in every summary() snapshot
        self.cache = cache
        self.latencies = []              # seconds, one per completed request
        self.completed = 0
        self.ticks = 0
        self.solve_time = 0.0            # seconds inside solver calls
        self.first_submit = None         # perf_counter, earliest completed
        self.last_done = None            # perf_counter, latest completion
        self.slots_offered = 0           # max_batch per tick
        self.admitted = 0
        self.useful_cells = 0.0          # Σ n_real * t_real over requests
        self.padded_cells = 0.0          # Σ slots * n_pad * t_pad over ticks
        self.per_bucket = {}             # bucket -> tick count
        self.depth_hist = {}             # realized depth -> request count
        self.layers_run = 0              # Σ while-loop trips over ticks
        self.adaptive_ticks = 0
        self.n_layers = 0                # L, for flops-saved denominators

    def record_tick(self, bucket, n_admitted, slots, useful_cells,
                    padded_cells, latencies, wall, depths=None,
                    layers_run=None, n_layers=None, done_at=None):
        """One solver invocation: ``n_admitted`` requests in ``slots``
        batch slots of ``bucket``, per-request submit→complete
        ``latencies`` (seconds), ``wall`` seconds in the solve, completed
        at ``done_at`` (``time.perf_counter``; now when omitted).
        Adaptive servers also pass per-request realized ``depths``, the
        tick's while-loop trip count ``layers_run`` and the model depth
        ``n_layers``."""
        self.ticks += 1
        self.completed += int(n_admitted)
        self.admitted += int(n_admitted)
        self.slots_offered += int(slots)
        self.solve_time += float(wall)
        self.useful_cells += float(useful_cells)
        self.padded_cells += float(padded_cells)
        self.latencies.extend(float(x) for x in latencies)
        if latencies:
            done_at = time.perf_counter() if done_at is None else done_at
            first = done_at - max(latencies)
            if self.first_submit is None or first < self.first_submit:
                self.first_submit = first
            if self.last_done is None or done_at > self.last_done:
                self.last_done = done_at
        key = tuple(bucket)
        self.per_bucket[key] = self.per_bucket.get(key, 0) + 1
        if depths is not None:
            self.adaptive_ticks += 1
            self.layers_run += int(layers_run)
            self.n_layers = int(n_layers)
            for d in depths:
                d = int(d)
                self.depth_hist[d] = self.depth_hist.get(d, 0) + 1

    def summary(self) -> dict:
        lat = np.asarray(self.latencies, np.float64)
        span = (self.last_done - self.first_submit
                if self.last_done is not None else 0.0)
        out = {
            "requests_completed": self.completed,
            "ticks": self.ticks,
            "federations_per_sec": (self.completed / span
                                    if span > 0 else 0.0),
            "latency_p50_ms": (float(np.percentile(lat, 50)) * 1e3
                               if lat.size else 0.0),
            "latency_p99_ms": (float(np.percentile(lat, 99)) * 1e3
                               if lat.size else 0.0),
            "occupancy": (self.admitted / self.slots_offered
                          if self.slots_offered else 0.0),
            "pad_waste": (1.0 - self.useful_cells / self.padded_cells
                          if self.padded_cells > 0 else 0.0),
            "per_bucket_ticks": {f"n{n}xt{t}": c
                                 for (n, t), c in
                                 sorted(self.per_bucket.items())},
        }
        if self.cache is not None:
            out["bucket_cache"] = dict(self.cache.stats())
        if self.adaptive_ticks:
            total_depth = sum(d * c for d, c in self.depth_hist.items())
            n_req = sum(self.depth_hist.values())
            L_ = max(self.n_layers, 1)
            out.update({
                "depth_hist": {str(d): c for d, c in
                               sorted(self.depth_hist.items())},
                "mean_depth": total_depth / max(n_req, 1),
                "request_flops_saved": 1.0 - total_depth / (max(n_req, 1)
                                                            * L_),
                "batch_flops_saved": 1.0 - self.layers_run / (
                    self.adaptive_ticks * L_),
            })
        return out
