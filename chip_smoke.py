#!/usr/bin/env python3
"""Bring-up smoke run of SURF's main path on a TPU, at the paper's widths.

  python chip_smoke.py             # one chip: train -> evaluate -> serve
  python chip_smoke.py --chips 4   # four chips: agent-sharded training and
                                   # the request-sharded server, only

One chip, in one process, in this order:

  0. the per-layer mini-batch rows sampled on the chip
     (``unroll.sample_layer_batches``) against a host numpy gather;
  1. ``surf.train_surf`` for 5 meta-steps with the dense mixer;
  2. the same 5 steps with ``mix="pallas"`` (the compiled graph-filter
     kernel), its loss history checked against phase 1;
  3. ``surf.evaluate_surf`` on 4 held-out federations;
  4. a ``serve.FederationServer(mix="pallas")`` answering 8 new n=100
     federations through ``tick()``, each checked against
     ``surf.solve_federation`` on the same cohort.

``--chips 4`` runs only what exists across chips: the halo-sharded
5-step history (``train_surf(mesh=make_surf_mesh(1, 4), mix="halo")``,
25 agents per chip) against a one-device dense run of the same seed, and
a mesh-sharded server's answers against ``solve_federation``, with the
server's outputs checked to be split over the 4 chips.

The configuration is ``configs.surf_paper.PAPER`` unchanged (n=100, F=512,
C=10, b=10, K=2, L=10, degree-3 regular graph, 45/15 rows per agent); only
the meta-training pool is cut, to Q=8 synthetic federations from the
paper's 600. Weights are random, from a fixed seed.

Every line but the last is a JSON reading: per phase its compile seconds
(JAX's trace, lower and compile events, persistent-cache loads included),
cold and warm wall seconds after ``block_until_ready``, the devices' peak
bytes in use, parity deltas with their tolerances, and the Pallas mixer
tag. They are readings for a benchmark, not metrics. The last line names
the device. With no TPU, or when any check fails, the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import jax
import numpy as np

STEPS = 5
N_POOL = 8              # meta-training federations (the paper's pool: 600)
N_HELD = 4              # held-out federations for evaluate_surf
N_REQUESTS = 8          # new federations the server answers in one tick

# Dense XLA and the Mosaic kernel both run float32 matmuls at the TPU's
# default precision (bfloat16 operands, float32 accumulation) but round at
# different points, so the two paths are not bit-exact: per-layer outputs
# differ at ~2^-8 relative, compounding over L layers. Losses are held to
# 2% relative (+1e-3 absolute); accuracies to 0.02 absolute, i.e. 30 of
# the 1,500 test predictions of an n=100 federation (15 rows per agent).
TOL = {"loss_rtol": 2e-2, "loss_atol": 1e-3, "acc_atol": 2e-2}

# PAPER's lr_theta=1e-2 does not train at the paper's widths: the first
# Adam step moves every entry of each layer's 53M-entry M by about lr, and
# the test loss jumps from 2.35 to ~3e9 on both paths. In that regime each
# step multiplies the paths' rounding differences about tenfold (relative
# loss deltas 4e-7, 2e-4, 1.7e-3, 1.5e-2, 0.13 over steps 0-4 on a v5e),
# so only the first two losses compare the kernels: step 0 is the L-layer
# forward, step 1 follows one backward pass through the kernel's custom
# VJP and one Adam step. Accuracy is bounded and is compared at every step.
LOSS_PARITY_STEPS = 2

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    loads included: they run inside the backend-compile event), summed
    from ``jax.monitoring`` duration events since the last ``take()``."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def take(self):
        s, self.seconds = self.seconds, 0.0
        return s


def emit(record):
    print(json.dumps(record), flush=True)


def fail(what):
    raise SystemExit(f"chip_smoke: FAILED: {what}")


def peak_bytes(devices):
    """``peak_bytes_in_use`` of each device (None where the backend keeps
    no statistics, as the CPU does)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def device_ids(tree):
    """Ids of the devices holding the first leaf of ``tree``."""
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return sorted(d.id for d in leaf.sharding.device_set)


def timed(fn):
    """(result, wall seconds) of ``fn()`` with its result blocked on."""
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def max_delta(a, b, rtol, atol):
    """(largest |a - b|, whether every |a - b| <= atol + rtol·|b|)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
        return math.inf, False
    diff = np.abs(a - b)
    return float(diff.max()), bool(np.all(diff <= atol + rtol * np.abs(b)))


def parity(loss, ref_loss, acc, ref_acc):
    """Loss and accuracy deltas against the reference, held to ``TOL``."""
    d_loss, ok_loss = max_delta(loss, ref_loss, TOL["loss_rtol"],
                                TOL["loss_atol"])
    d_acc, ok_acc = max_delta(acc, ref_acc, 0.0, TOL["acc_atol"])
    return {"max_dloss": d_loss, "max_dacc": d_acc, "tol": TOL}, (
        ok_loss and ok_acc)


def column(hist, key):
    return [float(h[key]) for h in hist]


def pallas_mixer(platform):
    """Tag and mode of the mixer ``mix="pallas"`` builds. On a TPU the
    kernel must be compiled, never interpreted."""
    from repro.kernels.graph_filter import make_pallas_mix
    from repro.kernels.graph_filter.ops import resolve_interpret
    tag = make_pallas_mix().tag
    interpret = resolve_interpret(None)
    if platform == "tpu" and (interpret or tag[-1] is not False):
        fail(f"the Pallas mixer runs in interpret mode on a TPU "
             f"(tag={tag})")
    return {"tag": list(tag), "interpret": bool(interpret)}


def train_phase(clock, name, cfg, pool, devices, **kw):
    """Cold then warm ``train_surf`` for ``STEPS`` meta-steps; returns the
    warm (state, hist, S) and the phase's reading."""
    from repro.core import surf

    def run():
        return surf.train_surf(cfg, pool, steps=STEPS, log_every=1, **kw)
    clock.take()
    out, cold = timed(run)
    compile_s = clock.take()
    del out                   # two TrainStates do not fit beside the scan
    (state, hist, S), warm = timed(run)
    losses = column(hist, "test_loss")
    if len(hist) != STEPS or not all(
            map(math.isfinite, losses + column(hist, "lagrangian"))):
        fail(f"{name}: short or non-finite loss history {hist}")
    return (state, hist, S), {
        "phase": name, "compile_s": compile_s, "cold_s": cold,
        "warm_s": warm, "test_loss": losses,
        "test_acc": column(hist, "test_acc"),
        "devices": device_ids(state.theta),
        "peak_bytes": peak_bytes(devices)}


def history_parity(hist, ref):
    k = LOSS_PARITY_STEPS
    return parity(column(hist, "test_loss")[:k], column(ref, "test_loss")[:k],
                  column(hist, "test_acc"), column(ref, "test_acc"))


def sample_rows_phase(cfg, dataset):
    """``unroll.sample_layer_batches`` (a one-hot contraction) compiled
    for the device, against a host numpy gather of the same uniform
    indices, at the config's shape: the rows must be equal bit for bit."""
    from repro.core import unroll as U
    key = jax.random.PRNGKey(0)
    Xtr, Ytr = dataset["Xtr"], dataset["Ytr"]
    Xl, Yl = jax.jit(lambda k, x, y: U.sample_layer_batches(k, x, y, cfg))(
        key, Xtr, Ytr)
    idx = np.asarray(jax.random.randint(
        key, (cfg.n_layers, cfg.n_agents, cfg.batch_per_agent), 0,
        Xtr.shape[1]))
    agents = np.arange(cfg.n_agents)[None, :, None]
    exact = bool(np.array_equal(np.asarray(Xl), Xtr[agents, idx])
                 and np.array_equal(np.asarray(Yl), Ytr[agents, idx]))
    emit({"phase": "sample_rows", "shape": list(Xl.shape), "exact": exact})
    if not exact:
        fail("sample_layer_batches' rows differ from a host gather")


def new_federations(cfg, n):
    """Federations the model never saw: a fresh graph and dataset each, at
    the config's n."""
    from repro.core import surf
    from repro.data import synthetic
    out = []
    for i in range(n):
        _, S = surf.make_problem(cfg, seed=10_000 + i)
        out.append({"S": np.asarray(S), "seed": i,
                    "ds": synthetic.sample_dataset(cfg, seed=20_000 + i)})
    return out


def served_sharding(server, bucket, request):
    """Sharding of the per-request outputs of the executable ``tick()``
    runs for ``bucket``: the server's cached bucket solver (a cache hit,
    checked), run as ``warm()`` runs it, on a batch assembled from
    ``request``'s device slot under all-false masks."""
    misses = server.cache_stats()["misses"]
    solve = server._solver(bucket, server.max_batch)
    if server.cache_stats()["misses"] != misses:
        fail(f"the server built a new executable for {bucket}")
    slot = server._slot(request["S"], request["ds"], bucket,
                        request["seed"], 0)
    args, mask, t_real = server._batch(bucket, server.max_batch, [slot])
    out = solve(args[0], server.theta, *args[1:], mask, t_real)
    return out["final_loss"].sharding


def serve_phase(clock, name, cfg, state, requests, devices, mesh=None):
    """Warm a ``FederationServer`` for the requests' bucket, answer all of
    them in one ``tick()``, and check each against ``solve_federation``."""
    from repro.core import surf
    from repro.serve import FederationServer
    server = FederationServer(cfg, state.theta, mix="pallas",
                              max_batch=len(requests), mesh=mesh)

    def answer():
        futs = [server.submit(r["S"], r["ds"], seed=r["seed"])
                for r in requests]
        served = server.tick()
        if served != len(requests) or not all(f.done() for f in futs):
            fail(f"{name}: one tick answered {served} of {len(requests)}")
        return [f.result() for f in futs]
    # cold: compile the bucket's executable and the admission-time
    # featurization, then answer; warm: answer the same requests again
    clock.take()
    (buckets, _), cold = timed(lambda: (
        server.warm([(cfg.n_agents, cfg.test_per_agent)]), answer()))
    compile_s = clock.take()
    results, warm = timed(answer)
    out_sharding = served_sharding(server, buckets[0], requests[0])
    refs = [surf.solve_federation(cfg, state, r["S"], r["ds"],
                                  seed=r["seed"]) for r in requests]
    par, ok = parity([r["final_loss"] for r in results],
                     [r["final_loss"] for r in refs],
                     [r["final_acc"] for r in results],
                     [r["final_acc"] for r in refs])
    reading = {"phase": name, "compile_s": compile_s, "cold_s": cold,
               "warm_s": warm, "requests": len(requests),
               "n_agents": cfg.n_agents,
               "federations_per_sec":
                   server.metrics.summary()["federations_per_sec"],
               **par, "mixer": pallas_mixer(devices[0].platform),
               "output_devices": sorted(
                   d.id for d in out_sharding.device_set),
               "output_shard": list(out_sharding.shard_shape(
                   (server.max_batch,))),
               "peak_bytes": peak_bytes(devices)}
    emit(reading)
    if not ok:
        fail(f"{name}: served federations vs solve_federation: {par}")
    return reading


def run_one_chip(cfg, *, n_pool=N_POOL, n_held=N_HELD,
                 n_requests=N_REQUESTS):
    """Phases 1-4 on ``jax.devices()[0]``; exits on a failed check."""
    from repro.core import surf
    from repro.data import synthetic
    devices = jax.devices()[:1]
    clock = CompileClock()
    pool = synthetic.make_meta_dataset(cfg, n_pool, seed=0)
    held = synthetic.make_meta_dataset(cfg, n_held, seed=1)
    sample_rows_phase(cfg, pool[0])

    (state, hist_dense, _), r = train_phase(clock, "train_dense", cfg, pool,
                                            devices)
    emit(r)
    del state

    (state, hist, S), r = train_phase(clock, "train_pallas", cfg, pool,
                                      devices, mix="pallas")
    par, ok = history_parity(hist, hist_dense)
    emit({**r, **par, "mixer": pallas_mixer(devices[0].platform)})
    if not ok:
        fail(f"train_pallas history vs train_dense: {par}")

    clock.take()
    _, cold = timed(lambda: surf.evaluate_surf(cfg, state, S, held))
    compile_s = clock.take()
    ev, warm = timed(lambda: surf.evaluate_surf(cfg, state, S, held))
    emit({"phase": "evaluate", "compile_s": compile_s, "cold_s": cold,
          "warm_s": warm, "federations": n_held,
          "final_loss": float(ev["final_loss"]),
          "final_acc": float(ev["final_acc"]),
          "peak_bytes": peak_bytes(devices)})
    if (ev["acc_per_layer"].shape != (cfg.n_layers,) or not all(
            np.all(np.isfinite(v)) for v in ev.values())):
        fail(f"evaluate_surf: {ev}")

    serve_phase(clock, "serve", cfg, state, new_federations(cfg, n_requests),
                devices)


def run_four_chips(cfg, *, n_pool=N_POOL, n_requests=N_REQUESTS):
    """The cross-chip phases: halo-sharded training against a one-device
    dense run of the same seed, then the request-sharded server against
    ``solve_federation``; exits on a failed check."""
    from repro.data import synthetic
    from repro.launch.mesh import make_surf_mesh
    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--chips 4 needs 4 devices, found {devices}")
    all_ids = sorted(d.id for d in devices)
    clock = CompileClock()
    pool = synthetic.make_meta_dataset(cfg, n_pool, seed=0)

    (state, hist_dense, _), r = train_phase(clock, "train_dense_1dev", cfg,
                                            pool, devices)
    emit(r)
    if r["devices"] != [devices[0].id]:
        fail(f"the one-device run is on {r['devices']}, not on "
             f"{devices[0].id}")
    del state

    mesh = make_surf_mesh(1, 4, n_agents=cfg.n_agents)
    (state, hist, _), r = train_phase(clock, "train_halo_4dev", cfg, pool,
                                      devices, mesh=mesh, mix="halo")
    par, ok = history_parity(hist, hist_dense)
    emit({**r, **par, "mesh": dict(mesh.shape),
          "agents_per_device": cfg.n_agents // mesh.shape["agent"]})
    if r["devices"] != all_ids:
        fail(f"the halo-sharded state is on {r['devices']}, not on "
             f"{all_ids}")
    if not ok:
        fail(f"train_halo_4dev history vs one-device dense: {par}")

    r = serve_phase(clock, "serve_4dev", cfg, state,
                    new_federations(cfg, n_requests), devices, mesh=mesh)
    if (r["output_devices"] != all_ids
            or r["output_shard"] != [n_requests // len(all_ids)]):
        fail(f"the server's outputs are not split over {all_ids}: "
             f"devices {r['output_devices']}, shard {r['output_shard']}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train -> evaluate -> serve on one chip; 4: "
                         "only the agent-sharded training and "
                         "request-sharded serving phases")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {device} (platform "
              f"{device.platform!r}); this script checks the chip and never "
              "falls back to another backend", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs.surf_paper import PAPER
    from repro.utils.cache import use_compilation_cache
    emit({"compilation_cache": use_compilation_cache(),
          "config": {k: getattr(PAPER, k) for k in (
              "n_agents", "n_layers", "feature_dim", "n_classes",
              "batch_per_agent", "filter_taps", "train_per_agent",
              "test_per_agent", "topology", "degree", "lr_theta")},
          "pool": N_POOL})
    if args.chips == 4:
        run_four_chips(PAPER)
    else:
        run_one_chip(PAPER)
    devices = jax.devices()
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
