"""From a profiler trace to the device's busy time, its idle share and the
``breakdown``.

``load_xplane`` turns JAX's ``.xplane.pb`` into a small plain record:

    {"device_ops": [[name, start_ns, dur_ns], ...],   # one list per chip
     "host_spans": [[name, start_ns, dur_ns], ...],   # bench.* annotations
     "op_text": {name: the start of its HLO text}}

``reduce_trace`` works on that record alone, so that it can be checked on
a small recorded trace without a chip:

  * busy: the union of the intervals in which an operation runs on a
    chip, inside the traced window, averaged over the chips;
  * idle share: 1 − busy / window;
  * top device operations by summed self time: an operation that holds
    others (a ``while`` around its body) is charged only for the time
    none of them runs;
  * idle gaps: each stretch of the window in which no operation ran on a
    chip, charged to the benchmark's host span that overlaps it most
    (``(no span)`` where none does), summed by span name.

Operations are named as the trace names them, cut to the HLO
instruction's name (``%fusion.12``, not its whole text).
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
NO_SPAN = "(no span)"


def load_xplane(trace_dir):
    """The plain record of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    chips, spans, text = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for ln in plane.lines:
                if ln.name != OPS_LINE:
                    continue
                for e in ln.events:
                    name = op_name(e.name)
                    text.setdefault(name, e.name[:300])
                    ops.append([name, e.start_ns, e.duration_ns])
            chips.append(ops)
        elif plane.name.startswith("/host:"):
            spans.extend([e.name[len(SPAN_PREFIX):], e.start_ns,
                          e.duration_ns]
                         for ln in plane.lines for e in ln.events
                         if e.name.startswith(SPAN_PREFIX))
    return {"device_ops": chips, "host_spans": spans, "op_text": text}


def op_name(text):
    """``%fusion.12`` of ``%fusion.12 = f32[...] fusion(...)``."""
    return text.split(" = ", 1)[0]


def self_times(events, lo, hi):
    """{name: seconds·1e9} of the events of one line clipped to [lo, hi),
    each less the time of the events nested inside it."""
    clipped = sorted(((max(s, lo), min(s + d, hi), n) for n, s, d in events
                      if min(s + d, hi) > max(s, lo)),
                     key=lambda e: (e[0], -e[1]))
    out, stack = {}, []
    for s, e, n in clipped:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]] -= min(e, stack[-1][1]) - s
        out[n] = out.get(n, 0.0) + (e - s)
        stack.append((s, e, n))
    return out


def charge_gaps(gap_list, spans):
    """{span name: idle ns}: each gap charged to the span that overlaps it
    most. Gaps and spans are swept in order of start."""
    spans = sorted(spans, key=lambda x: x[1])
    out, active, i = {}, [], 0
    for g0, g1 in gap_list:
        while i < len(spans) and spans[i][1] < g1:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] > g0]
        best, cover = NO_SPAN, 0.0
        for n, s, e in active:
            c = _overlap(g0, g1, s, e)
            if c > cover:
                best, cover = n, c
        out[best] = out.get(best, 0.0) + (g1 - g0)
    return out


def union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi), in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(merged, lo, hi):
    """The stretches of [lo, hi) that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce_trace(record, window_span="window", top=10):
    """{busy_s, window_s, idle_share, device_ops, idle_gaps} of the traced
    window, the host span named ``window_span``."""
    spans = record["host_spans"]
    win = [(s, s + d) for n, s, d in spans if n == window_span]
    if len(win) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(win)}")
    lo, hi = win[0]
    others = [(n, s, s + d) for n, s, d in spans if n != window_span]
    chips = record["device_ops"]
    if not chips:
        raise RuntimeError("the trace holds no device plane")
    busy, ops, idle = 0.0, {}, {}
    for events in chips:
        merged = union([(s, s + d) for _, s, d in events], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, t in self_times(events, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + t
        for name, t in charge_gaps(gaps(merged, lo, hi), others).items():
            idle[name] = idle.get(name, 0.0) + t
    n = len(chips)
    window_s = (hi - lo) * 1e-9
    busy_s = busy / n * 1e-9

    def ranked(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": ranked(ops), "idle_gaps": ranked(idle)}
