"""Amortized-solver serving: batch-solve NEW federations at request
rate.

SURF's trained unrolled network solves an unseen federation in one
forward pass (amortization, paper §4).  This package operationalizes
that: requests (a mixing matrix + a cohort dataset) are featurized at
their true shape, padded into shape buckets, continuously batched and
solved through per-bucket compiled executables — one trace per bucket,
zero at request rate.

    server = FederationServer(cfg, state.theta, mix="pallas")
    server.warm([(n, t), ...])           # compile ahead of traffic
    fut = server.submit(S, dataset, seed=0)
    server.tick()                        # or drain()
    fut.result()["final_acc"]

Layers: ``solver`` (the jitted request-vmapped masked forward;
``mesh=`` shards the request axis over devices, and a mesh with a
'theta' axis splits θ's perceptron by columns —
``launch.mesh.serve_mesh(devices, cfg)`` picks the layout from θ's
bytes and the device's memory), ``buckets`` (shape
bucketing + provably-inert padding, on the device), ``queue``
(continuous batching of device-resident slots + futures,
deadline-aware admission), ``driver`` (``AsyncDriver`` — a
background tick thread so ``submit`` returns immediately), ``metrics``
(throughput/latency/pad-waste/cache telemetry).  The benchmark's
serving cells drive it from ``bench/``.

Where a tick's time goes: run the server under a profiler session,

    with jax.profiler.trace("serve-trace"):
        ...submit and tick...
    recs = repro.utils.spans.records()

and each ``submit`` leaves ``serve.submit`` (attribute ``req``, the
request's id) with ``serve.submit.featurize`` and ``serve.submit.pad``
inside it, and each ``tick`` leaves ``serve.tick`` (``reqs``, the ids it
admitted; ``bucket``) with ``serve.tick.admit``, ``.stack``, ``.call``
(``bytes_in``, host bytes handed to the solver: the slots' masks and
``t_real``, since request data is uploaded once, at ``submit``;
``devices``, ``theta_bytes`` and ``gather_bytes``, what each device of
the mesh streams and receives),
``.wait`` and ``.unpack`` (one transfer of the outputs, then the
per-request split) inside it.  Each is a record in ``recs`` (start, end,
thread CPU seconds, parent) and a ``surf.*`` host event in the profile,
beside the solver's device operations, which carry the ``surf/mix``,
``surf/perceptron``, ``surf/loss`` and, with θ split, ``surf/gather``
scopes.  Without a profiler
session nothing is recorded (``repro.utils.spans``).
"""
from repro.serve.buckets import (Bucket, BucketSpec, pad_cohort, pad_probe,
                                 slot_mask)
from repro.serve.driver import AsyncDriver
from repro.serve.metrics import ServeMetrics
from repro.serve.queue import FederationServer, ServeFuture
from repro.serve.solver import (SERVE_MIXES, make_bucket_solver,
                                request_shardings, resolve_serve_mix,
                                serve_cache_key)

__all__ = ["Bucket", "BucketSpec", "pad_cohort", "pad_probe", "slot_mask",
           "AsyncDriver", "ServeMetrics", "FederationServer",
           "ServeFuture", "SERVE_MIXES", "make_bucket_solver",
           "request_shardings", "resolve_serve_mix", "serve_cache_key"]
