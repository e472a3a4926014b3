"""Serving job on all of a cell's chips: open-loop arrivals of new
federations into the program's ``FederationServer``, ticked by its
``AsyncDriver``, on the mesh the program lays out for the configuration
(``repro.launch.mesh.serve_mesh``: request slots side by side, or θ's
perceptron split by columns when one chip cannot hold it).

First the job looks for that layout in the program; a program without it
cannot run the cell, and the job stops before it builds anything. Set-up
then makes θ on the devices, already laid out: each layer from
``surfgen.theta_layer``, its columns zero-padded to the split and placed
by ``out_shardings``, so that no chip ever holds θ whole (threefry keys
split by position, so the values do not depend on the layout). The
window, the drain and the record are those of ``jobs/serve.py``, whose
``HostWatch``, ``Load``, ``wrap_tick`` and ``served`` it uses.

In a traced run the job also reads, from each chip's ``XLA Modules`` line
of the profile, the device time of the server's bucket executable
(``jit_solve_s``) inside the window and how often it ran: the seconds
that ``mfu.serve_mesh`` and ``theta_roofline.serve_mesh`` divide by.

Then, with the server and its θ freed, a sample of the answered requests
drawn from the seed is solved again on one chip by the plain reference
computed by layer blocks (``reference_blocked``) and compared.

Faults for ``calibrate.py``: those of ``jobs/serve.py`` (``no_mix``,
``half_batch``, ``altered``) and ``shard_swap``, θ made with its first
two column blocks exchanged (the blocks of two chips where θ is split,
else its two halves).
"""
from __future__ import annotations

import gc
import glob
import math
import os
import re
import threading
import time

import numpy as np

import flops
import harness
import program
import reference
import reference_blocked
import surfgen
import trace_reduce

_serve = harness.load_module("jobs", "serve")
HostWatch, Load, wrap_tick, served = (_serve.HostWatch, _serve.Load,
                                      _serve.wrap_tick, _serve.served)
DRAIN_S = _serve.DRAIN_S
# the module name of the server's bucket executable (``jax.jit`` of the
# program's ``solve_s``) on the profile's ``XLA Modules`` lines
SOLVER_MODULE = re.compile(r"jit_solve_s\b")
MODULES_LINE = "XLA Modules"


def layout_api():
    """The program's serving layout: ``serve_mesh`` and the θ rules. A
    program that lacks them cannot run this job."""
    try:
        from repro.launch.mesh import serve_mesh
        from repro.sharding.surf_rules import (padded_columns,
                                               theta_shardings, theta_split)
    except ImportError as e:
        raise harness.BenchError(
            f"the program has no serving layout for several chips ({e})")
    return serve_mesh, padded_columns, theta_shardings, theta_split


def make_theta(key, cfg, scale, mesh, swap=False):
    """θ = {h, M, d} on ``mesh``'s devices, laid out as the server takes
    it, from ``surfgen.theta_layer`` layer by layer; ``swap`` exchanges
    its first two column blocks (the ``shard_swap`` fault)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    _, padded_columns, theta_shardings, theta_split = layout_api()
    split = theta_split(mesh)
    d, _ = surfgen.dims(cfg)
    cols = padded_columns(d, split)

    def exchange(a):
        if split > 1:
            perm = [(0, 1), (1, 0)] + [(i, i) for i in range(2, split)]
            spec = P(*([None] * (a.ndim - 1)), "theta")
            return jax.shard_map(
                lambda b: jax.lax.ppermute(b, "theta", perm), mesh=mesh,
                in_specs=spec, out_specs=spec)(a)
        h = cols // 2
        return jnp.concatenate([a[..., h:2 * h], a[..., :h], a[..., 2 * h:]],
                               -1)

    def layer(k, l):
        p = surfgen.theta_layer(k, cfg, l, scale)
        out = {"h": p["h"],
               "M": jnp.pad(p["M"], ((0, 0), (0, cols - d))),
               "d": jnp.pad(p["d"], (0, cols - d))}
        if swap:
            out["M"], out["d"] = exchange(out["M"]), exchange(out["d"])
        return out

    build = jax.jit(lambda k: jax.lax.map(
        lambda l: layer(k, l), jnp.arange(cfg["n_layers"])),
        out_shardings=theta_shardings(mesh))
    return build(surfgen.theta_key(key))


def solver_on_device(trace_dir, window="window"):
    """(seconds, runs) per chip of the bucket executable inside the traced
    ``window`` span: its device time, clipped to the window, and the
    count of its runs there, each averaged over the chips; (None, 0)
    where the profile holds no such run."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    win, chips = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            chips.append([(e.start_ns, e.end_ns) for ln in plane.lines
                          if ln.name == MODULES_LINE for e in ln.events
                          if SOLVER_MODULE.match(e.name)])
        elif plane.name.startswith("/host:"):
            win += [(e.start_ns, e.end_ns) for ln in plane.lines
                    for e in ln.events
                    if e.name == trace_reduce.SPAN_PREFIX + window]
    if len(win) != 1:
        return None, 0
    return runs_inside(chips, *win[0])


def runs_inside(chips, lo, hi):
    """(seconds, runs) per chip of the [start, end) ns intervals of
    ``chips`` (one list a chip) clipped to [lo, hi); (None, 0) where no
    interval meets it."""
    inside = [[(max(a, lo), min(b, hi)) for a, b in runs if b > lo and a < hi]
              for runs in chips]
    if not any(inside):
        return None, 0
    seconds = sum(b - a for runs in inside for a, b in runs) * 1e-9
    return seconds / len(chips), sum(map(len, inside)) / len(chips)


def run(cell, seed, seconds, trace, devices, fault=None):
    """One run of a serving cell on all of ``devices``; see ``run.py``
    for the result."""
    serve_mesh = layout_api()[0]
    import jax
    from repro.engine.core import TRACE_COUNTS
    from repro.serve import AsyncDriver, FederationServer
    cfg, params = served(cell)
    traffic = harness.load_module("traffic", params["kind"])
    clock = harness.CompileClock()
    spans = harness.Spans(annotate=trace)
    key = harness.seed_key(seed)

    t_setup = time.perf_counter()
    mesh = serve_mesh(devices, program.config(cfg))
    theta = make_theta(key, cfg, cfg["theta_scale"], mesh,
                       swap=fault == "shard_swap")
    feds = traffic.federations(key, cfg, int(params["federations"]), seed)
    server = FederationServer(program.config(cfg), theta,
                              max_batch=int(params["max_batch"]), mesh=mesh)
    server.warm([(cfg["n_agents"], cfg["test_per_agent"])])
    warm_fut = server.submit(*feds[0], seed=len(feds) + 10 ** 6)
    server.tick()
    warm_fut.result()
    setup_s = time.perf_counter() - t_setup
    compile_s, _ = clock.take()
    traces_before = TRACE_COUNTS["serve"]

    due = traffic.due_times(float(params["rate"]), seconds, seed)
    load = Load(due, feds, fault)
    wrap_tick(server, load, spans, fault)
    ticker = AsyncDriver(server).start()
    trace_dir = harness.trace_dir(cell["name"]) if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=harness.profile_options())
    solved0, ticks0 = server.metrics.solve_time, server.metrics.ticks
    admitted0 = server.metrics.admitted
    slots0 = server.metrics.slots_offered
    t0 = time.perf_counter()
    with spans.span("window"), HostWatch(t0) as watch:
        clients = [threading.Thread(target=load.client,
                                    args=(ticker, t0, spans))
                   for _ in range(int(params["clients"]))]
        for c in clients:
            c.start()
        for c in clients:
            c.join()
        limit = time.perf_counter() + DRAIN_S
        while not load.all_done() and time.perf_counter() < limit:
            time.sleep(0.002)
    solver_s, solver_runs = None, 0
    if trace:
        jax.profiler.stop_trace()
        solver_s, solver_runs = solver_on_device(trace_dir)
    ticker.stop(drain=False, timeout_s=DRAIN_S)
    _, window_compiles = clock.take()
    peak = harness.peak_bytes(devices)
    m = server.metrics
    ticks = m.ticks - ticks0
    solve_s = m.solve_time - solved0
    occupancy = (m.admitted - admitted0) / max(m.slots_offered - slots0, 1)
    layout = {k: int(v) for k, v in mesh.shape.items()}

    done = ~np.isnan(load.done_at)
    lat = np.where(done, load.done_at - (t0 + due), np.inf)
    n_done = int(done.sum())
    span_s = float(np.nanmax(load.done_at) - t0) if n_done else math.inf
    late = load.submit_at - (t0 + due)
    worst_late = int(np.nanargmax(late))

    rng = np.random.default_rng([int(seed), 5])
    sample = rng.choice(np.flatnonzero(done),
                        size=min(int(params["compare"]), n_done),
                        replace=False)
    answers = {int(i): dict(load.futures[i].result()) for i in sample}
    del server, ticker, load.futures, theta
    gc.collect()
    with jax.default_device(devices[0]):
        gaps = compare(cfg, key, feds, answers, np.float32)
    checks = [(k, gaps[k], limit) for k, limit in cell["limits"].items()]
    checks.append(("never_answered", len(due) - n_done, 0))
    checks.append(("window_compiles", window_compiles, 0))
    checks.append(("window_traces", TRACE_COUNTS["serve"] - traces_before,
                   0))
    return {
        "attempted": len(due),
        "failed": len(due) - n_done,
        "checks": checks,
        "e2e": {"setup_s": setup_s,
                "federations_per_s": n_done / span_s,
                "solve_p95_ms": 1e3 * float(np.percentile(lat, 95)),
                "peak_hbm_gib.serve": peak / 2 ** 30},
        "peak_bytes": peak,
        "trace_dir": trace_dir,
        "spans": spans,
        "ctx": {"occupancy": occupancy,
                "ticks": ticks,
                "solve_s": solve_s, "solved": n_done,
                "request_flops": flops.solve_flops(cfg),
                "devices": len(devices), "compile_s": compile_s,
                "solver_device_s": solver_s, "solver_runs": solver_runs},
        "info": {"gaps": gaps, "layout": layout, "requests": len(due),
                 "answered": n_done, "ticks": ticks, "span_s": span_s,
                 "late_mean_ms": 1e3 * float(np.nanmean(late)),
                 "late_max_ms": 1e3 * float(late[worst_late]),
                 "late_max_at_s": float(due[worst_late]),
                 "submit_max_ms": 1e3 * max(spans.durations("submit"),
                                            default=0.0),
                 "tick_max_ms": 1e3 * max(spans.durations("tick"),
                                          default=0.0),
                 **watch.record(),
                 "latency_p50_ms": 1e3 * float(np.percentile(lat, 50)),
                 "occupancy": occupancy},
    }


def compare(cfg, key, feds, answers, dtype):
    """Layer-blocked reference answers in ``dtype`` for the sampled
    requests, and the gaps of ``answers`` to them."""
    refs = reference_blocked.solve_requests(
        cfg, key, [(*feds[i % len(feds)], i) for i in answers], dtype)
    return reference.serve_gaps(list(answers.values()), refs)


def calibrate(cell, seed, devices, faults=(), with_program=True,
              with_control=True, seconds=5.0):
    """Gaps of the program (a short window at the cell's own load), of each
    planted fault and of the control (the layer-blocked reference in
    bfloat16) against the float32 reference, for one seed
    (``calibrate.py``)."""
    import jax
    import jax.numpy as jnp
    rows = []
    for kind in ([None] if with_program else []) + list(faults):
        r = run(cell, seed, seconds, False, devices, fault=kind)
        rows.append({"kind": kind or "program", **r["info"]["gaps"],
                     "never_answered": r["failed"]})
    if not with_control:
        return rows
    cfg, params = served(cell)
    traffic = harness.load_module("traffic", params["kind"])
    key = harness.seed_key(seed)
    feds = traffic.federations(key, cfg, int(params["federations"]), seed)
    requests = [(*feds[i % len(feds)], i)
                for i in range(int(params["compare"]))]
    with jax.default_device(devices[0]):
        control = reference_blocked.solve_requests(cfg, key, requests,
                                                   jnp.bfloat16)
        answers = {i: {"W": W, "final_loss": loss, "final_acc": acc}
                   for i, (W, loss, acc) in enumerate(control)}
        rows.append({"kind": "control",
                     **compare(cfg, key, feds, answers, np.float32)})
    return rows
