#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is found by its name (``bench/harness.py``).
With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the line carries
its per-layer metrics, the device's busy and window seconds and a
``breakdown``. Every run checks what the timed path produced against the
plain reference (``bench/reference.py``) and prints each number compared
beside its limit, last on standard error and last in the result line.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def per_layer(spec, cell, result, trace, peaks):
    """Each per-layer metric of the cell that its reader finds."""
    ctx = dict(result["ctx"], trace=trace, peaks=peaks,
               spans=result["spans"])
    out = {}
    for m in harness.metrics_for(spec, cell["name"], "per_layer"):
        value = harness.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_spec()
        cell = harness.load_cell(args.workload)
        devices = harness.require_devices(cell["chips"])
        peaks = harness.peaks_for(devices[0].device_kind)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.log(compile_cache=harness.use_compile_cache(),
                device=harness.device_record(devices))
    job = harness.load_module("jobs", cell["job"])
    result = job.run(cell, args.seed, args.seconds, bool(args.trace),
                     devices)
    device = dict(harness.device_record(devices),
                  memory_peak_bytes=result["peak_bytes"])
    line = {"attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        import trace_reduce
        rec = trace_reduce.load_xplane(result["trace_dir"])
        shutil.rmtree(result["trace_dir"], ignore_errors=True)
        red = trace_reduce.reduce_trace(rec)
        harness.log(top_ops={n: rec["op_text"][n] for n, _ in
                             red["device_ops"]})
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["metrics"] = per_layer(spec, cell, result, red, peaks)
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    else:
        line["metrics"] = {
            m["name"]: {"value": result["e2e"][m["name"]], "unit": m["unit"]}
            for m in harness.metrics_for(spec, cell["name"], "end_to_end")}
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in result["checks"]}
    correct = harness.verdict(result["checks"])
    harness.log(info=result.get("info"))
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(dict({"correct": correct}, **line, device=device,
                          checks=checks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
