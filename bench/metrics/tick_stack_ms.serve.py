"""Mean host time a tick spends stacking its batch (empty slots,
``np.stack``, mask), from the program's ``serve.tick.stack`` spans."""
import program_spans as ps


def read(ctx):
    return ps.mean_ms(ps.window_records(ctx), "serve.tick.stack")
