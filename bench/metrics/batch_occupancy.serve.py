"""Admitted requests over the batch slots the server's ticks offered in
the window (``ServeMetrics.admitted`` / ``slots_offered``)."""


def read(ctx):
    occupancy = ctx.get("occupancy")
    return None if occupancy is None else 100.0 * occupancy
