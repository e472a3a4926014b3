"""Mean per tick of wall less thread CPU time over the tick's admit,
stack and unpack spans: the tick thread waiting on the GIL or the
scheduler inside its own host work."""
import program_spans as ps


def read(ctx):
    return ps.tick_offcpu_ms(ps.window_records(ctx))
