"""The perceptron's share of its bandwidth roofline, the bound of a tick
at low occupancy: the bytes of θ each chip streams in one run of the
server's bucket executable (the ``theta_bytes`` counter of the program's
``serve.tick.call`` spans, from the shapes) times the runs on a chip
inside the window, over their device time on that chip (both from the
profile, ``jobs/serve_mesh.solver_on_device``), over one chip's HBM bytes
per second."""
import program_spans as ps


def read(ctx):
    calls = [r for r in ps.named(ps.window_records(ctx), "serve.tick.call")
             if "theta_bytes" in r.attrs]
    if not calls or not ctx.get("solver_device_s") or "peaks" not in ctx:
        return None
    per_run = sum(r.attrs["theta_bytes"] for r in calls) / len(calls)
    return 100.0 * per_run * ctx["solver_runs"] / ctx["solver_device_s"] / \
        ctx["peaks"]["hbm_bytes_per_s"]
