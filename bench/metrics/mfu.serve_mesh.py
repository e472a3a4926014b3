"""The served solves' share of the peak of all the cell's chips: model
FLOPs of the answered requests at their true size (``flops.solve_flops``)
over the device time per chip of the server's bucket executable inside
the window (from the profile, ``jobs/serve_mesh.solver_on_device``), over
the chips' count times one chip's bf16 peak."""


def read(ctx):
    if not ctx.get("solver_device_s") or not ctx.get("devices"):
        return None
    return 100.0 * ctx["request_flops"] * ctx["solved"] / \
        ctx["solver_device_s"] / (ctx["devices"] * ctx["peaks"]["bf16_flops"])
