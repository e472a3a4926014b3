"""The program's own spans (``repro.utils.spans``) that start inside the
benchmark's ``window`` span, and the numbers the per-layer metrics read
from them.

The program records spans only while a profiler session runs, so a
``--trace 0`` run, and a program without ``repro.utils.spans``, give no
records, and every reader then returns ``None``.
"""
from __future__ import annotations

import numpy as np

TICK_HOST = ("serve.tick.admit", "serve.tick.stack", "serve.tick.unpack")


def program_records():
    """Every span the program recorded; none where it records none."""
    try:
        from repro.utils.spans import records
    except ImportError:
        return []
    return records()


def window_records(ctx, records=None):
    """The records (``program_records()`` unless given) whose ``t0`` falls
    inside the one ``window`` span of the benchmark's spans in ``ctx``."""
    spans = ctx.get("spans")
    win = [(t0, t1) for n, t0, t1 in getattr(spans, "records", ())
           if n == "window"]
    if len(win) != 1:
        return []
    lo, hi = win[0]
    recs = program_records() if records is None else records
    return [r for r in recs if lo <= r.t0 < hi]


def named(recs, name):
    return [r for r in recs if r.name == name]


def mean_ms(recs, name):
    """Mean wall milliseconds of the spans called ``name``."""
    d = [r.t1 - r.t0 for r in named(recs, name)]
    return 1e3 * sum(d) / len(d) if d else None


def queue_wait_p95_ms(recs):
    """p95 over requests of the start of the ``serve.tick`` that admitted
    the request less the end of its ``serve.submit``, in ms."""
    submitted = {r.attrs["req"]: r.t1 for r in named(recs, "serve.submit")}
    waits = [tick.t0 - submitted[i] for tick in named(recs, "serve.tick")
             for i in tick.attrs.get("reqs", ()) if i in submitted]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None


def tick_offcpu_ms(recs):
    """Mean over the ticks that admitted requests of wall less thread CPU
    time summed over the tick's admit, stack and unpack spans, in ms: the
    time the tick thread waited (for the GIL, or not scheduled) inside its
    own host work."""
    ticks = [t for t in named(recs, "serve.tick") if t.attrs.get("reqs")]
    if not ticks:
        return None
    phases = [r for r in recs if r.name in TICK_HOST]
    off = [sum(r.t1 - r.t0 - r.cpu_s for r in phases
               if r.thread == t.thread and t.t0 <= r.t0 and r.t1 <= t.t1)
           for t in ticks]
    return 1e3 * sum(off) / len(off)


def upload_mb(recs):
    """Host bytes handed to the solver call per answered request, in MB
    (10^6 bytes): Σ ``bytes_in`` of ``serve.tick.call`` over the requests
    the ticks admitted."""
    calls = named(recs, "serve.tick.call")
    answered = sum(len(t.attrs.get("reqs", ()))
                   for t in named(recs, "serve.tick"))
    if not calls or not answered:
        return None
    return sum(r.attrs["bytes_in"] for r in calls) / answered / 1e6
