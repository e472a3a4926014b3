"""The served solves' share of the chip's bf16 peak: model FLOPs of the
answered requests at their true size (``flops.solve_flops``) over the
seconds the server spent in its solver calls, over the peak."""


def read(ctx):
    if not ctx.get("solve_s"):
        return None
    return 100.0 * ctx["request_flops"] * ctx["solved"] / ctx["solve_s"] / \
        ctx["peaks"]["bf16_flops"]
