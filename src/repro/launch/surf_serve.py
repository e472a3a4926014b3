"""Amortized-solver serving driver (``make bench-serve`` /
``scripts/bench.sh serve``): meta-train once, then replay a synthetic
request trace — NEW federations (fresh topology + cohort dataset per
request, ragged sizes) — through ``repro.serve``'s continuous-batching
server, and write machine-readable ``bench_out/BENCH_serve.json``.

The run ASSERTS the three claims that make the numbers trustworthy:

  1. trace economy — warming k shape buckets traces the serve body
     EXACTLY k times, and the whole replay (hundreds of requests)
     traces ZERO more (``engine.TRACE_COUNTS["serve"]``);
  2. parity — EVERY request's served result matches the single-cohort
     reference solve (``core.surf.solve_federation`` at the request's
     true shape) despite bucket padding and batching;
  3. coverage — the trace spans >= 2 shape buckets and >= 200 requests
     (the acceptance floor for the serving claim).

Backend + resolved Pallas interpret mode are stamped into the JSON like
``BENCH_kernels.json`` — on CPU the kernel path is interpret-mode, so
absolute throughput is a correctness-path number, not accelerator perf.

A ``sharded_async`` section then replays a trace prefix per shard count
through a MESH-SHARDED server (request axis placed over 'agent'-axis
devices, ``serve.request_shardings``) driven by ``serve.AsyncDriver`` —
federations/s vs shards + tick counts + parity spot-checks, with
``jax.device_count()``/mesh fingerprints stamped and the simulated-
device caveat made explicit (forced host CPU devices share one chip).

  PYTHONPATH=src python -m repro.launch.surf_serve --requests 220
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro import engine as E
from repro.configs.surf_paper import SMOKE, SPARSE_SMOKE
from repro.core import surf
from repro.core.tasks import resolve_task
from repro.kernels.graph_filter.ops import resolve_interpret
from repro.serve import BucketSpec, FederationServer
from repro.utils.cache import use_compilation_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--requests", type=int, default=220,
                    help="trace length (acceptance floor: 200)")
    ap.add_argument("--sizes", default="6,8,12,16",
                    help="cohort sizes the trace draws from")
    ap.add_argument("--rows", default="4,6",
                    help="test-rows-per-agent values the trace draws from")
    ap.add_argument("--dist", choices=("uniform", "zipf"), default="zipf",
                    help="cohort-size distribution (zipf skews small)")
    ap.add_argument("--mix", choices=("dense", "pallas"), default="dense")
    ap.add_argument("--task", choices=("classification", "sparse"),
                    default="classification")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--sharded-requests", type=int, default=64,
                    help="trace prefix replayed per sharded+async row "
                         "(0 disables the sharded section)")
    ap.add_argument("--steps", type=int, default=40,
                    help="meta-training steps before serving")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="output dir (default: $BENCH_OUT or bench_out)")
    return ap


def _size_probs(sizes, dist):
    if dist == "uniform":
        return np.full(len(sizes), 1.0 / len(sizes))
    ranks = np.argsort(np.argsort(sizes)) + 1.0      # small sizes first
    w = 1.0 / ranks ** 1.2
    return w / w.sum()


def synth_trace(cfg, task, sizes, rows, dist, n_requests, seed):
    """The synthetic request stream: per request a cohort size n and
    test-rows t from the configured distribution, a FRESH topology
    (request-indexed graph seed) and a FRESH dataset — every request is
    a federation the model has never seen (the amortization claim)."""
    rng = np.random.default_rng(seed)
    probs = _size_probs(sizes, dist)
    out = []
    for i in range(n_requests):
        n = int(rng.choice(sizes, p=probs))
        t = int(rng.choice(rows))
        cfg_r = dataclasses.replace(cfg, n_agents=n, test_per_agent=t)
        _, S = surf.make_problem(cfg_r, seed=10_000 + i)
        ds = task.synth_datasets(cfg_r, 1, seed=20_000 + i)[0]
        out.append({"cfg": cfg_r, "S": np.asarray(S), "ds": ds,
                    "seed": i % 16})
    return out


def bench_sharded_async(cfg, state, trace, args, sizes, rows, tol):
    """The sharded+async rows: replay a trace prefix through a
    mesh-sharded server (request axis over 'agent'-axis devices) driven
    by ``AsyncDriver``, one row per shard count — federations/s vs
    shards, tick count, and a per-row parity spot-check vs the
    solo reference solve.  On forced-host CPU devices the shards share
    one physical CPU, so rows track PLACEMENT overhead (zero-collective
    claim), not real scaling — the caveat is stamped."""
    from repro.launch.mesh import make_surf_mesh
    from repro.serve import AsyncDriver
    from repro.sharding.surf_rules import mesh_fingerprint
    ndev = jax.device_count()
    shard_counts = [s for s in (1, 2, 4, 8)
                    if s <= ndev and ndev % s == 0
                    and args.max_batch % s == 0]
    sub = trace[:args.sharded_requests]
    out = []
    for shards in shard_counts:
        mesh = make_surf_mesh(1, shards) if shards > 1 else None
        server = FederationServer(
            cfg, state.theta, mix=args.mix, max_batch=args.max_batch,
            buckets=BucketSpec(agent_sizes=(8, 16, 32),
                               row_sizes=(4, 8, 16)),
            mesh=mesh)
        server.warm((n, t) for n in sizes for t in rows)
        driver = AsyncDriver(server)
        with driver:
            t0 = time.perf_counter()
            futs = [driver.submit(req["S"], req["ds"], seed=req["seed"])
                    for req in sub]
            driver.wait(futs, timeout_s=300.0)
            wall = time.perf_counter() - t0
        max_d = 0.0
        for req, fut in zip(sub[:8], futs[:8]):
            ref = surf.solve_federation(req["cfg"], state, req["S"],
                                        req["ds"], seed=req["seed"])
            res = fut.result()
            max_d = max(max_d,
                        abs(float(res["final_loss"] - ref["final_loss"])),
                        abs(float(res["final_acc"] - ref["final_acc"])))
        assert max_d < tol, (
            f"sharded serve (shards={shards}) diverged from reference: "
            f"{max_d:.2e} (tol {tol})")
        stats = driver.stats()
        summary = server.metrics.summary()
        row = {"shards": shards,
               "mesh_fingerprint": mesh_fingerprint(mesh),
               "requests": len(sub),
               "federations_per_sec": summary["federations_per_sec"],
               "async_wall_s": round(wall, 3),
               "async_federations_per_sec": (len(sub) / wall
                                             if wall > 0 else 0.0),
               "ticks": stats["ticks"],
               "parity_spot_max_delta": max_d,
               "bucket_cache": server.cache_stats()}
        out.append(row)
        print(f"sharded+async shards={shards}: "
              f"{row['async_federations_per_sec']:.1f} federations/s "
              f"parity={max_d:.2e}")
    return out


def main(argv=None, parser=None):
    args = (parser or build_parser()).parse_args(argv)
    sizes = [int(s) for s in args.sizes.split(",")]
    rows = [int(r) for r in args.rows.split(",")]
    cfg = SPARSE_SMOKE if args.task == "sparse" else SMOKE
    task = resolve_task(cfg, None)
    interpret = resolve_interpret(None)
    backend = jax.default_backend()
    print(f"serve bench: backend={backend} mix={args.mix} "
          f"task={args.task} requests={args.requests}")

    # ---- meta-train once; the trained theta serves EVERY cohort size
    # (shared perceptron => permutation equivariance, Remark 5.1)
    mds = task.synth_datasets(cfg, 4, seed=args.seed)
    state, _, _ = surf.train_surf(cfg, mds, steps=args.steps,
                                  seed=args.seed, log_every=0)

    trace = synth_trace(cfg, task, sizes, rows, args.dist, args.requests,
                        args.seed)
    server = FederationServer(
        cfg, state.theta, mix=args.mix, max_batch=args.max_batch,
        buckets=BucketSpec(agent_sizes=(8, 16, 32), row_sizes=(4, 8, 16)))

    # ---- warm every bucket the trace can hit, counting body traces
    base = E.TRACE_COUNTS["serve"]
    warmed = server.warm((n, t) for n in sizes for t in rows)
    warm_traces = E.TRACE_COUNTS["serve"] - base
    n_buckets = len(warmed)
    print(f"warmed {n_buckets} buckets "
          f"{[f'n{b.n_agents}xt{b.rows}' for b in warmed]}: "
          f"{warm_traces} serve trace(s)")
    assert n_buckets >= 2, f"trace must span >= 2 buckets, got {n_buckets}"
    assert warm_traces == n_buckets, (                           # claim 1a
        f"expected ONE trace per warm bucket, got {warm_traces} for "
        f"{n_buckets} buckets")

    # ---- replay: interleave submits and ticks (continuous batching)
    base = E.TRACE_COUNTS["serve"]
    futures = []
    t0 = time.perf_counter()
    for i, req in enumerate(trace):
        futures.append(server.submit(req["S"], req["ds"],
                                     seed=req["seed"]))
        if (i + 1) % args.max_batch == 0:
            server.tick()
    server.drain()
    replay_wall = time.perf_counter() - t0
    replay_traces = E.TRACE_COUNTS["serve"] - base
    assert replay_traces == 0, (                                 # claim 1b
        f"replay retraced the serve body {replay_traces}x — warm buckets "
        "must serve the whole trace")
    assert all(f.done() for f in futures)

    # ---- parity: every request vs the single-cohort reference solve
    tol = 5e-4 if args.mix == "pallas" else 5e-5
    max_dloss = max_dacc = 0.0
    for req, fut in zip(trace, futures):
        ref = surf.solve_federation(req["cfg"], state, req["S"], req["ds"],
                                    seed=req["seed"])
        res = fut.result()
        max_dloss = max(max_dloss,
                        abs(float(res["final_loss"] - ref["final_loss"])))
        max_dacc = max(max_dacc,
                       abs(float(res["final_acc"] - ref["final_acc"])))
    assert max_dloss < tol and max_dacc < tol, (                 # claim 2
        f"serve/reference divergence: dloss={max_dloss:.2e} "
        f"dacc={max_dacc:.2e} (tol {tol})")
    print(f"parity over {len(trace)} requests: max dloss={max_dloss:.2e} "
          f"max dacc={max_dacc:.2e}")

    summary = server.metrics.summary()
    print(f"{summary['federations_per_sec']:.1f} federations/s  "
          f"p50={summary['latency_p50_ms']:.1f}ms "
          f"p99={summary['latency_p99_ms']:.1f}ms  "
          f"occupancy={summary['occupancy']:.2f} "
          f"pad_waste={summary['pad_waste']:.2f}")

    sharded_rows = (bench_sharded_async(cfg, state, trace, args, sizes,
                                        rows, tol)
                    if args.sharded_requests > 0 else [])

    out = {
        "backend": backend, "interpret": bool(interpret),
        "device_count": jax.device_count(),
        "simulated_devices": backend == "cpu",
        "sharding_caveat": ("forced host-platform CPU devices share one "
                            "physical CPU: sharded rows track placement "
                            "overhead (zero-collective claim), not real "
                            "scaling" if backend == "cpu" else
                            "real accelerator devices"),
        "timing_caveat": ("Pallas in interpret mode on CPU: absolute "
                          "times are NOT accelerator perf" if interpret
                          and args.mix == "pallas" else
                          "CPU correctness-path timing"),
        "mix": args.mix, "task": args.task,
        "requests": len(trace), "sizes": sizes, "rows": rows,
        "dist": args.dist, "max_batch": args.max_batch,
        "buckets": [f"n{b.n_agents}xt{b.rows}" for b in warmed],
        "trace_counts": {"warm_buckets": n_buckets,
                         "warm_traces": int(warm_traces),
                         "replay_traces": int(replay_traces),
                         "one_trace_per_warm_bucket":
                             bool(warm_traces == n_buckets)},
        "parity": {"checked": len(trace), "tol": tol,
                   "max_dloss": max_dloss, "max_dacc": max_dacc},
        "replay_wall_s": round(replay_wall, 3),
        "serve": summary,
        "bucket_cache": server.cache_stats(),
        "sharded_async": sharded_rows,
    }
    out_dir = args.out or os.environ.get("BENCH_OUT", "bench_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")
    return out


if __name__ == "__main__":
    use_compilation_cache()
    main()
