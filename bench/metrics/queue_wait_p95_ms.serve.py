"""p95 over requests of the wait from the end of ``submit`` to the start
of the tick that admitted the request, joined by request id from the
program's ``serve.submit`` and ``serve.tick`` spans."""
import program_spans as ps


def read(ctx):
    return ps.queue_wait_p95_ms(ps.window_records(ctx))
