"""Convergence-adaptive depth driver (``make bench-earlyexit`` /
``scripts/bench.sh earlyexit``): meta-train one overprovisioned-depth
model (descending constraints tightened so intermediate iterates are
anytime-usable), sweep ``exit_threshold`` through the early-exit
while-loop solver, and write ``bench_out/BENCH_earlyexit.json``.

The run ASSERTS the claims that make adaptive depth trustworthy — they
are hard failures, not recorded numbers:

  1. exit_threshold=0 parity — the adaptive path consumes the SAME
     pre-sampled per-layer batch stack (bit-for-bit RNG stream), runs
     depth == L exactly, and its W_L is allclose to ``udgd_forward``'s;
  2. trace economy — the while-loop solver traces ONCE per distinct
     threshold (``engine.TRACE_COUNTS["adaptive"]``), and re-evaluating
     a swept threshold adds ZERO traces;
  3. the frontier — at least one swept threshold achieves mean realized
     depth strictly < L with eval accuracy within ``--eps`` of the
     fixed-L baseline (the depth-vs-accuracy frontier rows are the fig5
     artifact);
  4. serve-path depth telemetry — replaying requests through an
     adaptive ``FederationServer`` populates the depth histogram
     (every request lands a realized depth) at one serve trace per warm
     bucket and zero at request rate.

Backend + resolved Pallas interpret mode are stamped like
``BENCH_kernels.json``.

  PYTHONPATH=src python -m repro.launch.surf_earlyexit --steps 600
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as E
from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.data import synthetic
from repro.kernels.graph_filter.ops import resolve_interpret
from repro.serve import BucketSpec, FederationServer
from repro.utils.cache import use_compilation_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--layers", type=int, default=12,
                    help="unrolled depth L (overprovisioned on purpose)")
    ap.add_argument("--min-layers", type=int, default=8,
                    help="realized-depth floor: stochastic unrolling "
                    "makes single-layer grad ratios noisy, so the "
                    "certificate is armed only past the depth where "
                    "this smoke model's iterates have converged")
    ap.add_argument("--thresholds", default="0.02,0.05,0.1,0.3",
                    help="exit_threshold sweep (fig5 frontier points)")
    ap.add_argument("--eps", type=float, default=0.04,
                    help="max |acc - fixed-L acc| for a threshold to "
                    "count as matched accuracy")
    ap.add_argument("--steps", type=int, default=600,
                    help="meta-training steps (needs enough dual-ascent "
                    "pressure for anytime iterates)")
    ap.add_argument("--pool", type=int, default=8,
                    help="downstream evaluation datasets")
    ap.add_argument("--eval-seeds", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12,
                    help="adaptive serve mini-trace length")
    ap.add_argument("--mix", choices=("dense", "pallas"), default="dense",
                    help="serve-leg mixer")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="output dir (default: $BENCH_OUT or bench_out)")
    return ap


def _mean(res, key):
    return float(np.mean(res[key]))


def main(argv=None, parser=None):
    args = (parser or build_parser()).parse_args(argv)
    thresholds = [float(t) for t in args.thresholds.split(",")]
    assert all(t > 0 for t in thresholds), "sweep thresholds must be > 0"
    interpret = resolve_interpret(None)
    backend = jax.default_backend()
    L = int(args.layers)
    # tightened dual ascent (lr_lambda, eps) vs the SMOKE defaults: the
    # descending constraints must BIND for intermediate iterates to be
    # anytime-usable — with loose duals all the accuracy arrives at
    # layer L and no early exit can match it
    cfg = dataclasses.replace(SMOKE, n_layers=L, min_layers=args.min_layers,
                              probe_size=4, lr_lambda=0.3, eps=0.1)
    task = resolve_task(cfg, None)
    print(f"earlyexit bench: backend={backend} L={L} "
          f"min_layers={args.min_layers} thresholds={thresholds}")

    mds = synthetic.make_meta_dataset(cfg, 4, seed=args.seed)
    state, _, S = surf.train_surf(cfg, mds, steps=args.steps,
                                  seed=args.seed, log_every=0)
    S = np.asarray(S)
    pool = synthetic.make_meta_dataset(cfg, args.pool, seed=77)
    seeds = list(range(args.eval_seeds))

    # ---- fixed-L baseline (the paper's forward)
    fixed = surf.evaluate_surf(cfg, state, S, pool, seeds=seeds)
    fixed_acc = _mean(fixed, "final_acc")
    fixed_loss = _mean(fixed, "final_loss")
    print(f"fixed-L baseline: acc={fixed_acc:.4f} loss={fixed_loss:.4f}")

    # ---- claim 1: exit_threshold=0 parity (depth==L, same stream/W_L)
    batch = {k: jnp.asarray(v) for k, v in pool[0].items()}
    key = jax.random.fold_in(jax.random.PRNGKey(1000 + args.seed), 0)
    W0, Xl, Yl = U.featurize_cohort(key, batch, cfg, task=task)
    W0b, Xlb, Ylb = U.featurize_cohort(key, batch, cfg, task=task)
    assert (np.array_equal(np.asarray(Xl), np.asarray(Xlb))
            and np.array_equal(np.asarray(Yl), np.asarray(Ylb))
            and np.array_equal(np.asarray(W0), np.asarray(W0b))), (
        "featurization is not a pure function of the key — RNG stream "
        "parity is broken")
    Xp, Yp = U.probe_batch(batch, cfg)
    W_fix, _ = U.udgd_forward(state.theta, S, W0, Xl, Yl, cfg)
    W_ad, depth0 = U.udgd_forward_adaptive(state.theta, S, W0, Xl, Yl,
                                           Xp, Yp, cfg)
    assert int(depth0) == L, (
        f"exit_threshold=0 must run all layers: depth {int(depth0)} != {L}")
    np.testing.assert_allclose(np.asarray(W_ad), np.asarray(W_fix),
                               rtol=1e-5, atol=1e-6)
    r0 = surf.evaluate_surf(cfg, state, S, pool, seeds=seeds,
                            depth="adaptive")
    assert _mean(r0, "depth") == float(L)
    np.testing.assert_allclose(_mean(r0, "final_acc"), fixed_acc,
                               rtol=1e-5, atol=1e-5)
    print(f"threshold=0 parity: depth=={L}, W_L allclose, stream exact")

    # ---- threshold sweep (claims 2 + 3)
    base_tr = E.TRACE_COUNTS["adaptive"]
    frontier = []
    for thr in thresholds:
        cfg_t = dataclasses.replace(cfg, exit_threshold=thr)
        r = surf.evaluate_surf(cfg_t, state, S, pool, seeds=seeds,
                               depth="adaptive")
        row = {"threshold": thr,
               "mean_depth": _mean(r, "depth"),
               "final_acc": _mean(r, "final_acc"),
               "final_loss": _mean(r, "final_loss"),
               "acc_gap": fixed_acc - _mean(r, "final_acc"),
               "layers_saved_frac": 1.0 - _mean(r, "depth") / L}
        frontier.append(row)
        print(f"thr={thr}: depth={row['mean_depth']:.2f}/{L} "
              f"acc={row['final_acc']:.4f} (gap {row['acc_gap']:+.4f})")
    sweep_traces = E.TRACE_COUNTS["adaptive"] - base_tr
    assert sweep_traces == len(thresholds), (                    # claim 2a
        f"expected ONE adaptive trace per threshold, got {sweep_traces} "
        f"for {len(thresholds)}")
    base_tr = E.TRACE_COUNTS["adaptive"]
    surf.evaluate_surf(dataclasses.replace(cfg, exit_threshold=thresholds[0]),
                       state, S, pool, seeds=seeds, depth="adaptive")
    assert E.TRACE_COUNTS["adaptive"] == base_tr, (              # claim 2b
        "re-evaluating a swept threshold retraced the while-loop solver")
    print(f"trace economy: {sweep_traces} traces for {len(thresholds)} "
          "thresholds, zero on re-eval")

    matched = [row for row in frontier
               if row["mean_depth"] < L and abs(row["acc_gap"]) <= args.eps]
    assert matched, (                                            # claim 3
        f"no swept threshold achieved mean depth < {L} within "
        f"eps={args.eps} of the fixed-L accuracy {fixed_acc:.4f}: "
        + json.dumps(frontier))
    chosen = max(matched, key=lambda row: row["layers_saved_frac"])
    print(f"chosen threshold {chosen['threshold']}: "
          f"{chosen['layers_saved_frac']:.0%} layers saved at "
          f"acc gap {chosen['acc_gap']:+.4f}")

    # ---- claim 4: adaptive serve mini-trace (depth telemetry + traces)
    cfg_s = dataclasses.replace(cfg, exit_threshold=chosen["threshold"])
    server = FederationServer(
        cfg_s, state.theta, mix=args.mix, max_batch=4,
        buckets=BucketSpec(agent_sizes=(cfg.n_agents,),
                           row_sizes=(cfg.test_per_agent,)),
        depth="adaptive")
    base_sv = E.TRACE_COUNTS["serve"]
    server.warm([(cfg.n_agents, cfg.test_per_agent)])
    warm_traces = E.TRACE_COUNTS["serve"] - base_sv
    assert warm_traces == 1, (
        f"adaptive serve warm traced {warm_traces}x, expected 1")
    base_sv = E.TRACE_COUNTS["serve"]
    futs = []
    for i in range(args.requests):
        cfg_r = dataclasses.replace(cfg_s, n_agents=cfg.n_agents)
        _, S_r = surf.make_problem(cfg_r, seed=10_000 + i)
        ds = task.synth_datasets(cfg_r, 1, seed=20_000 + i)[0]
        futs.append(server.submit(np.asarray(S_r), ds, seed=i % 8))
    server.drain()
    assert E.TRACE_COUNTS["serve"] == base_sv, "serve replay retraced"
    assert all(f.done() for f in futs)
    ssum = server.metrics.summary()
    n_hist = sum(ssum["depth_hist"].values())
    assert n_hist == args.requests, (
        f"depth histogram covers {n_hist} of {args.requests} requests")
    assert 0 < ssum["mean_depth"] <= L
    print(f"serve depth_hist={ssum['depth_hist']} "
          f"mean_depth={ssum['mean_depth']:.2f} "
          f"request_flops_saved={ssum['request_flops_saved']:.2f} "
          f"batch_flops_saved={ssum['batch_flops_saved']:.2f}")

    from repro.sharding.surf_rules import mesh_fingerprint
    out = {
        "backend": backend, "interpret": bool(interpret),
        "device_count": jax.device_count(),
        "simulated_devices": backend == "cpu",
        "mesh_fingerprint": mesh_fingerprint(None),
        "timing_caveat": ("Pallas in interpret mode on CPU: absolute "
                          "times are NOT accelerator perf" if interpret
                          and args.mix == "pallas" else
                          "CPU correctness-path run"),
        "n_layers": L, "min_layers": int(args.min_layers),
        "probe_size": int(cfg.probe_size), "steps": int(args.steps),
        "eps": float(args.eps), "mix": args.mix,
        "fixed": {"final_acc": fixed_acc, "final_loss": fixed_loss,
                  "depth": float(L)},
        "fig5_frontier": frontier,
        "chosen": chosen,
        "parity_thr0": {"depth": int(depth0), "w_allclose": True,
                        "stream_bit_identical": True},
        "trace_counts": {
            "thresholds_swept": len(thresholds),
            "adaptive_sweep_traces": int(sweep_traces),
            "adaptive_reeval_traces": 0,
            "serve_warm_traces": int(warm_traces),
            "serve_replay_traces": 0},
        "serve": ssum,
    }
    out_dir = args.out or os.environ.get("BENCH_OUT", "bench_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_earlyexit.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}")
    return out


if __name__ == "__main__":
    use_compilation_cache()
    main()
