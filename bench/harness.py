"""What every cell shares: finding its files by name, the device check,
the compile cache, the compile clock, host spans, the seed's keys and the
result line.

Files are found by the names in ``BENCHMARK.json``:

  * ``bench/workloads/<cell>.json`` — one cell: config, job, chips and the
    traffic parameters;
  * ``bench/configs/<config>.json`` — one configuration;
  * ``bench/jobs/<job>.py`` — one module per job kind, with ``run(...)``;
  * ``bench/traffic/<kind>.py`` — one generator per traffic kind;
  * ``bench/metrics/<metric>.py`` — one reader per per-layer metric.

A later cell of an existing job and traffic kind needs only new JSON files
and new ``BENCHMARK.json`` entries.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """A cell that cannot run as asked: a missing file, no chip."""


def _read_json(kind, name):
    path = os.path.join(BENCH, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``bench/<kind>/<name>.py`` as a module; names may hold dots."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    """The cell's own file with its configuration folded in under
    ``"cfg"``."""
    cell = _read_json("workloads", name)
    cell["name"] = name
    cell["cfg"] = _read_json("configs", cell["config"])
    return cell


def load_spec():
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise BenchError(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        return json.load(f)


def metrics_for(spec, cell_name, group):
    """The entries of ``spec[group]`` that this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def require_devices(chips):
    """The TPU devices the cell runs on; no accelerator, or fewer chips
    than the cell asks for, is an error and never a fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: jax.devices()[0] is {devices[0]} "
                         f"(platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def use_compile_cache():
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``<checkout>/.jax_cache``), with every
    program kept, however fast it compiled, so that set-up stays the same
    from run to run."""
    import jax
    from repro.utils.cache import use_compilation_cache
    path = use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Seconds and count of JAX's trace, lower and compile events (cache
    loads run inside the compile event) since the last ``take()``."""

    def __init__(self):
        import jax
        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1

    def take(self):
        out = (self.seconds, self.events)
        self.seconds, self.events = 0.0, 0
        return out


class Spans:
    """Host spans of the benchmark's own calls. Each is kept in memory
    (name, start, end on ``time.perf_counter``) and, while a profiler
    trace runs, also written into it as a ``TraceAnnotation`` named
    ``bench.<name>``, so that idle gaps in the device trace can be
    attributed to them."""

    def __init__(self, annotate=False):
        self.annotate = annotate
        self.records = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        t1 = time.perf_counter()
        with self._lock:
            self.records.append((name, t0, t1))

    def durations(self, name):
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


def seed_key(seed):
    """A raw uint32[2] PRNG key holding all 64 bits of ``seed``."""
    import jax.numpy as jnp
    import numpy as np
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise BenchError(f"--seed must be in [0, 2**64), got {seed}")
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def peak_bytes(devices):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_record(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks_for(kind):
    """The published peaks of ``device_kind`` from ``bench/peaks.json``;
    a kind that is not in the table is an error, not a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def log(**kw):
    print(json.dumps(kw), file=sys.stderr, flush=True)


def trace_dir(cell_name):
    """Where a traced run writes its profile: inside the checkout, at a
    fixed path, emptied before the run and removed after it is read."""
    import shutil
    path = os.path.join(ROOT, ".bench_trace", cell_name)
    shutil.rmtree(path, ignore_errors=True)
    return path


def profile_options():
    """Device and host tracers on, the Python tracer off (it records every
    Python call and would swamp the host)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def verdict(checks):
    """``correct``: every number compared at or under its limit (a NaN is
    over any limit)."""
    return all(value <= limit for _, value, limit in checks)
