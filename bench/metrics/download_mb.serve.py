"""Bytes brought to the host per answered request, in MB (10^6 bytes):
Σ ``bytes_out`` of the program's ``serve.tick.unpack`` spans over the
requests the ticks answered. A program whose unpack spans carry no
``bytes_out`` gives no number."""
import program_spans as ps


def read(ctx):
    recs = ps.window_records(ctx)
    out = [r.attrs["bytes_out"] for r in ps.named(recs, "serve.tick.unpack")
           if "bytes_out" in r.attrs]
    answered = sum(len(t.attrs.get("reqs", ()))
                   for t in ps.named(recs, "serve.tick"))
    if not out or not answered:
        return None
    return sum(out) / answered / 1e6
