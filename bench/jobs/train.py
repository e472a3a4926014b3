"""Meta-training job: the compiled scan that ``train_surf`` runs
(``engine.scan.make_train_scan``), driven one meta-step per call with the
donated state carried from call to call, in chunks between host waits.

Set-up builds that one object: θ and the pool made on the device from the
seed, the program's ``TrainState`` and its compiled scan. It drives the
first ``CHECK_STEPS`` meta-steps through the window's own call and feed
(federations 0, 1, 2 of the pool, all different rows) and reads from the
state what the reference is compared on; then the same object runs the
window. After the window, with the program's state freed, the reference
(``reference.train_readings``) follows the same first steps from the seed.
"""
from __future__ import annotations

import math
import time

import numpy as np

import flops
import harness
import program
import reference
import surfgen

CHECK_STEPS = 3
ADAM_B1 = 0.9


def scalar(x):
    return float(np.asarray(x).reshape(-1)[-1])


class Trainer:
    """The program's compiled scan with its state, its pool and its key.
    ``fault`` breaks the timed path underneath, for the harness's own
    tests: ``"frozen"`` (each call returns the state it was given),
    ``"half_batch"`` (the test split's first half of the rows only),
    ``"no_mix"`` (the exchange between agents left out: S = I),
    ``"no_dual"`` (unconstrained: λ held at 0 after every call)."""

    def __init__(self, cfg, params, seed, traffic, fault=None):
        import jax
        import jax.numpy as jnp
        from repro.engine.core import TrainState
        from repro.engine.scan import make_train_scan
        from repro.optim import adam
        self.cfg = cfg
        self.key = harness.seed_key(seed)
        theta = surfgen.make_theta(self.key, cfg, cfg["theta_scale"])
        self.state = TrainState(
            theta=theta, lam=jnp.zeros((cfg["n_layers"],)),
            opt_state=adam(cfg["lr_theta"]).init(theta),
            step=jnp.zeros((), jnp.int32))
        self.pool = traffic.make(self.key, cfg, params)
        S = surfgen.mixing_matrix(cfg, seed)
        if fault == "no_mix":
            S = np.eye(S.shape[0], dtype=np.float32)
        if fault == "half_batch":
            t = cfg["test_per_agent"] // 2
            self.pool = dict(self.pool, Xte=self.pool["Xte"][:, :, :t],
                             Yte=self.pool["Yte"][:, :, :t])
        self.S = jnp.asarray(S)
        run = make_train_scan(program.config(cfg), self.S,
                              stacked=self.pool)
        if fault == "frozen":
            def frozen(state, pool, key, steps):
                copy = jax.tree_util.tree_map(jnp.copy, state)
                return (state,) + tuple(run(copy, pool, key, steps)[1:])
            self.run = frozen
        elif fault == "no_dual":
            def no_dual(state, pool, key, steps):
                out = run(state, pool, key, steps)
                return (out[0]._replace(lam=jnp.zeros_like(out[0].lam)),) + \
                    tuple(out[1:])
            self.run = no_dual
        else:
            self.run = run
        self._first_grad = jax.jit(lambda m: {
            k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - ADAM_B1)
            for k, v in m.items()})

    def step(self):
        """One meta-step; returns its metrics, (1,)-stacks on the device."""
        self.state, metrics, _ = self.run(self.state, self.pool, self.key, 1)
        return metrics

    def first_readings(self, steps=CHECK_STEPS):
        """The program's readings over its first ``steps`` meta-steps:
        each step's test loss and duals λ, per-leaf norms of the first
        gradient as Adam got it (m / (1 − β1) after one step) and of θ's
        change."""
        losses, lams, grad = [], [], None
        for t in range(steps):
            losses.append(scalar(self.step()["test_loss"]))
            lams.append(np.asarray(self.state.lam, np.float64).tolist())
            if t == 0:
                grad = reference.host(self._first_grad(
                    self.state.opt_state["m"]))
        change = reference.host(surfgen.change_norms(
            self.state.theta, self.key, self.cfg))
        return {"loss": losses, "lam": lams, "grad": grad, "change": change}

    def free(self):
        del self.state, self.pool, self.S, self.run


def window(trainer, seconds, chunk, spans):
    """Meta-steps in chunks of ``chunk`` calls; while chunk i runs, the
    host waits for chunk i − 1. Ends at the first chunk boundary past
    ``seconds``. Returns (steps, window seconds, last losses)."""
    steps, prev, losses = 0, None, []
    t0 = time.perf_counter()
    with spans.span("window"):
        while True:
            with spans.span("dispatch"):
                for _ in range(chunk):
                    cur = trainer.step()["test_loss"]
            steps += chunk
            if prev is not None:
                with spans.span("wait"):
                    losses.append(scalar(prev))
            prev = cur
            if time.perf_counter() - t0 >= seconds:
                break
        with spans.span("wait"):
            losses.append(scalar(prev))
    return steps, time.perf_counter() - t0, losses


def run(cell, seed, seconds, trace, devices, fault=None):
    """One run of a training cell; see ``run.py`` for the result."""
    import jax
    from repro.engine.core import TRACE_COUNTS
    cfg, params = dict(cell["cfg"]), cell["traffic"]
    traffic = harness.load_module("traffic", params["kind"])
    cfg["meta_pool"] = traffic.pool_size(params, cfg)
    clock = harness.CompileClock()
    spans = harness.Spans(annotate=trace)
    t_setup = time.perf_counter()
    trainer = Trainer(cfg, params, seed, traffic, fault=fault)
    prog = trainer.first_readings()
    setup_s = time.perf_counter() - t_setup
    compile_s, _ = clock.take()
    traces_before = TRACE_COUNTS["meta_step"]

    trace_dir = harness.trace_dir(cell["name"]) if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=harness.profile_options())
    steps, window_s, losses = window(trainer, seconds,
                                     int(params["steps_per_chunk"]), spans)
    if trace:
        jax.profiler.stop_trace()
    _, window_compiles = clock.take()
    peak = harness.peak_bytes(devices)
    trainer.free()
    del trainer

    ref = reference.train_readings(cfg, harness.seed_key(seed), CHECK_STEPS,
                                   np.float32)
    gaps = reference.train_gaps(prog, ref)
    limits = cell["limits"]
    checks = [(k, gaps[k], limit) for k, limit in limits.items()]
    checks.append(("window_compiles", window_compiles, 0))
    checks.append(("window_traces",
                   TRACE_COUNTS["meta_step"] - traces_before, 0))
    finite = all(map(math.isfinite, losses))
    rate = steps / window_s
    step_flops = flops.meta_step_flops(cfg)
    return {
        "attempted": steps + CHECK_STEPS,
        "failed": 0 if finite else 1,
        "checks": checks,
        "e2e": {"setup_s": setup_s, "meta_steps_per_s": rate,
                "peak_hbm_gib.train": peak / 2 ** 30},
        "peak_bytes": peak,
        "trace_dir": trace_dir,
        "spans": spans,
        "ctx": {"steps_per_s": rate, "step_flops": step_flops,
                "compile_s": compile_s},
        "info": {"prog": prog, "ref": ref, "gaps": gaps,
                 "window_losses": losses[-3:], "steps": steps,
                 "window_s": window_s},
    }


def calibrate(cell, seed, devices, faults=(), with_program=True,
              with_control=True):
    """Gaps of the program, of each planted fault and of the control
    (the reference in bfloat16) against the float32 reference, for one
    seed at the cell's own size (``calibrate.py``)."""
    import jax.numpy as jnp
    cfg, params = dict(cell["cfg"]), cell["traffic"]
    traffic = harness.load_module("traffic", params["kind"])
    cfg["meta_pool"] = traffic.pool_size(params, cfg)
    readings = {}
    for kind in ([None] if with_program else []) + list(faults):
        t0 = time.perf_counter()
        trainer = Trainer(cfg, params, seed, traffic, fault=kind)
        readings[kind or "program"] = trainer.first_readings()
        trainer.free()
        del trainer
        readings[kind or "program"]["seconds"] = time.perf_counter() - t0
    key = harness.seed_key(seed)
    t0 = time.perf_counter()
    ref = reference.train_readings(cfg, key, CHECK_STEPS, np.float32)
    ref_s = time.perf_counter() - t0
    if with_control:
        readings["control"] = reference.train_readings(cfg, key, CHECK_STEPS,
                                                       jnp.bfloat16)
    for kind, r in readings.items():
        yield {"kind": kind, **reference.train_gaps(r, ref),
               "loss": r["loss"], "ref_loss": ref["loss"],
               "grad": r["grad"], "ref_grad": ref["grad"],
               "lam": r["lam"][0], "ref_lam": ref["lam"][0],
               "change": r["change"], "ref_change": ref["change"],
               "seconds": r.get("seconds"), "ref_seconds": ref_s,
               "peak_bytes": harness.peak_bytes(devices)}
