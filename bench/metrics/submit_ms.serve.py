"""Mean host time of one ``submit`` (featurize at the true shape, pad into
the bucket, enqueue), from the benchmark's spans around each call."""


def read(ctx):
    d = ctx["spans"].durations("submit") if "spans" in ctx else []
    return 1e3 * sum(d) / len(d) if d else None
