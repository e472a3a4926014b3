"""Share of the traced serving window in which no operation ran on the
chip: 1 − (union of device op intervals) / window, from the profiler
trace (``trace_reduce.reduce_trace``)."""


def read(ctx):
    trace = ctx.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
