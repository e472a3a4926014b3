"""End-to-end LM training driver (example application + integration proof).

Trains any ``--arch`` (reduced variant by default) on the synthetic token
pipeline for N steps with checkpointing.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as CKPT
from repro.configs import get_config
from repro.data.pipeline import TokenPipeline
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.utils import tree_size


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"vocab={cfg.vocab}")

    key = jax.random.PRNGKey(0)
    params = M.init_lm(cfg, key)
    print(f"params: {tree_size(params)/1e6:.2f}M")
    step_fn, opt = make_train_step(cfg, lr=args.lr, remat=False)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    opt_state = opt.init(params)

    pipe = iter(TokenPipeline(cfg.vocab, args.batch, args.seq, seed=0))
    losses = []
    t0 = time.time()
    for t in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
        if cfg.layout == "encdec":
            batch["frames"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, t), (args.batch, 24, cfg.d_model))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if t % args.log_every == 0 or t == args.steps - 1:
            print(f"step {t:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{(time.time()-t0)/(t+1):.3f}s/step", flush=True)
    if args.ckpt:
        CKPT.save(args.ckpt, {"params": params, "step": args.steps})
        print(f"saved checkpoint to {args.ckpt}")
    head = sum(losses[:5]) / min(5, len(losses))
    tail = sum(losses[-5:]) / min(5, len(losses))
    assert tail < head, f"loss did not decrease: {head:.4f} -> {tail:.4f}"
    print(f"done: loss {head:.4f} -> {tail:.4f} (5-step means)")
    return losses


if __name__ == "__main__":
    main()
