"""Mean host time a tick spends bringing its results to the host, request
by request, from the program's ``serve.tick.unpack`` spans."""
import program_spans as ps


def read(ctx):
    return ps.mean_ms(ps.window_records(ctx), "serve.tick.unpack")
