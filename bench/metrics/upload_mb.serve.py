"""Host bytes passed to the solver call per answered request, in MB, from
the ``bytes_in`` counter of the program's ``serve.tick.call`` spans."""
import program_spans as ps


def read(ctx):
    return ps.upload_mb(ps.window_records(ctx))
