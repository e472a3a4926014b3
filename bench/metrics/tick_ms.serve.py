"""Mean seconds of one bucket-executable call in the window, from the
server's own ``ServeMetrics.solve_time`` over its ticks."""


def read(ctx):
    if not ctx.get("ticks"):
        return None
    return 1e3 * ctx["solve_s"] / ctx["ticks"]
