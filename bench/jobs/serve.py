"""Serving job: open-loop arrivals of new federations into the program's
``FederationServer``, ticked by its ``AsyncDriver``.

Set-up makes θ on the device and the cell's set of federations from the
seed, builds the server and its ``AsyncDriver``, and warms the one bucket the
traffic uses by answering one request through ``submit`` → ``tick``.
In the window, ``clients`` threads submit each request at its due time;
a request's latency runs from its due time to the end of the tick that
answered it. After the window closes, every request still open is given
``drain_s`` seconds; one that never comes counts as failed.

Then, with the server and its θ freed, a sample of the answered requests
drawn from the seed is solved again by the plain reference
(``reference.make_solve``) and compared.
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

import flops
import harness
import program
import reference
import surfgen

DRAIN_S = 60.0
BEAT_S = 0.02


class HostWatch:
    """Host stalls in the window, for the run's record: the garbage
    collector's pauses (``gc.callbacks``), and the longest overshoot of a
    ``BEAT_S`` sleep in a thread of its own, which a stall of the whole
    process shows (the GIL held long, or the process not scheduled)."""

    def __init__(self, t0):
        self.t0 = t0
        self.gc = []                     # (generation, start, seconds)
        self.beat = (0.0, 0.0)           # (overshoot, when)
        self._gc_start = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self._gc_start is not None:
            self.gc.append((info["generation"], self._gc_start - self.t0,
                            now - self._gc_start))
            self._gc_start = None

    def _beat(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(BEAT_S)
            over = time.perf_counter() - t - BEAT_S
            if over > self.beat[0]:
                self.beat = (over, t - self.t0)

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def record(self):
        worst = max(self.gc, key=lambda g: g[2], default=(None, None, 0.0))
        return {"gc_count": len(self.gc),
                "gc_full": sum(g[0] == 2 for g in self.gc),
                "gc_total_ms": 1e3 * sum(g[2] for g in self.gc),
                "gc_max_ms": 1e3 * worst[2], "gc_max_at_s": worst[1],
                "stall_max_ms": 1e3 * self.beat[0],
                "stall_at_s": self.beat[1]}


class Load:
    """The requests of one window and what happened to each."""

    def __init__(self, due, feds, fault=None):
        self.due = due
        self.feds = feds
        self.fault = fault
        n = len(due)
        self.submit_at = np.full(n, np.nan)
        self.done_at = np.full(n, np.nan)
        self.futures = [None] * n
        self._next = 0
        self._open = {}
        self._lock = threading.Lock()

    def request(self, i):
        S, ds = self.feds[i % len(self.feds)]
        if self.fault == "no_mix":
            S = np.eye(S.shape[0], dtype=np.float32)
        if self.fault == "half_batch":
            t = ds["Xte"].shape[1] // 2
            ds = dict(ds, Xte=ds["Xte"][:, :t], Yte=ds["Yte"][:, :t])
        return S, ds

    def client(self, ticker, t0, spans):
        while True:
            with self._lock:
                i = self._next
                if i >= len(self.due):
                    return
                self._next += 1
            wait = t0 + self.due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            S, ds = self.request(i)
            self.submit_at[i] = time.perf_counter()
            with spans.span("submit"):
                fut = ticker.submit(S, ds, seed=i)
            with self._lock:
                self.futures[i] = fut
                if fut.done():
                    self.done_at[i] = time.perf_counter()
                else:
                    self._open[i] = fut

    def stamp(self):
        """After a tick: the end time of every request it answered."""
        now = time.perf_counter()
        with self._lock:
            for i in [i for i, f in self._open.items() if f.done()]:
                self.done_at[i] = now
                del self._open[i]

    def all_done(self):
        with self._lock:
            return (self._next >= len(self.due) and not self._open
                    and all(f is not None for f in self.futures))


def wrap_tick(server, load, spans, fault):
    """The server's tick inside a host span, stamping what it answered;
    the ``"altered"`` fault changes one answer where it is produced."""
    tick = server.tick

    def traced_tick():
        with spans.span("tick"):
            done = tick()
        if fault == "altered" and done:
            for f in load.futures:
                if f is not None and f.done() and not getattr(
                        f, "_altered", False):
                    for k in ("W", "final_loss"):
                        f._result[k] = f._result[k] * 1.01
                    f._altered = True
                    break
        load.stamp()
        return done
    server.tick = traced_tick


def served(cell):
    """The cell's configuration, with θ drawn at the configuration's
    ``served_theta_scale`` where it states one, and its traffic."""
    cfg = dict(cell["cfg"])
    cfg["theta_scale"] = cfg.get("served_theta_scale", cfg["theta_scale"])
    return cfg, cell["traffic"]


def new_server(cfg, theta, params):
    from repro.serve import FederationServer
    return FederationServer(program.config(cfg), theta,
                            max_batch=int(params["max_batch"]))


def run(cell, seed, seconds, trace, devices, fault=None):
    """One run of a serving cell; see ``run.py`` for the result."""
    import jax
    from repro.engine.core import TRACE_COUNTS
    from repro.serve import AsyncDriver
    cfg, params = served(cell)
    traffic = harness.load_module("traffic", params["kind"])
    clock = harness.CompileClock()
    spans = harness.Spans(annotate=trace)
    key = harness.seed_key(seed)

    t_setup = time.perf_counter()
    theta = surfgen.make_theta(key, cfg, cfg["theta_scale"])
    feds = traffic.federations(key, cfg, int(params["federations"]), seed)
    server = new_server(cfg, theta, params)
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
    if trace:
        jax.profiler.stop_trace()
    ticker.stop(drain=False, timeout_s=DRAIN_S)
    _, window_compiles = clock.take()
    peak = harness.peak_bytes(devices)
    m = server.metrics
    ticks = m.ticks - ticks0
    solve_s = m.solve_time - solved0
    occupancy = (m.admitted - admitted0) / max(m.slots_offered - slots0, 1)

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
    gaps = compare(cfg, key, feds, answers, np.float32)
    limits = cell["limits"]
    checks = [(k, gaps[k], limit) for k, limit in limits.items()]
    checks.append(("never_answered", len(due) - n_done, 0))
    checks.append(("window_compiles", window_compiles, 0))
    checks.append(("window_traces", TRACE_COUNTS["serve"] - traces_before,
                   0))
    req_flops = flops.solve_flops(cfg)
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
                "request_flops": req_flops, "compile_s": compile_s},
        "info": {"gaps": gaps, "requests": len(due), "answered": n_done,
                 "ticks": ticks, "span_s": span_s,
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
    """Reference answers for the sampled requests (θ made again from the
    seed) and their gaps to the served ones."""
    theta = reference._cast(surfgen.make_theta(key, cfg, cfg["theta_scale"]),
                            dtype)
    solve = reference.make_solve(cfg, dtype)
    refs = [reference.serve_reference(cfg, theta, *feds[i % len(feds)], i,
                                      solve) for i in answers]
    return reference.serve_gaps(list(answers.values()), refs)


def calibrate(cell, seed, devices, faults=(), with_program=True,
              with_control=True, seconds=5.0):
    """Gaps of the program (a short window at the cell's own load), of each
    planted fault and of the control (the reference in bfloat16) against
    the float32 reference, for one seed (``calibrate.py``)."""
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
    n = int(params["compare"])
    # the control: the bfloat16 reference in the program's place
    theta = reference._cast(surfgen.make_theta(key, cfg, cfg["theta_scale"]),
                            jnp.bfloat16)
    solve = reference.make_solve(cfg, jnp.bfloat16)
    answers = {}
    for i in range(n):
        W, loss, acc = reference.serve_reference(
            cfg, theta, *feds[i % len(feds)], i, solve)
        answers[i] = {"W": W, "final_loss": loss, "final_acc": acc}
    del theta
    rows.append({"kind": "control",
                 **compare(cfg, key, feds, answers, np.float32)})
    return rows
