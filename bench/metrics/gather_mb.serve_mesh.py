"""Mean MB (10^6 bytes) each chip receives a tick from the all-gathers of
W's columns over a split θ, from the ``gather_bytes`` counter of the
program's ``serve.tick.call`` spans."""
import program_spans as ps


def read(ctx):
    calls = [r for r in ps.named(ps.window_records(ctx), "serve.tick.call")
             if "gather_bytes" in r.attrs]
    if not calls:
        return None
    return sum(r.attrs["gather_bytes"] for r in calls) / len(calls) / 1e6
