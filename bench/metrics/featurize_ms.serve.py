"""Mean host time of ``submit``'s featurization (dataset upload, the W0
and mini-batch draws, the results brought to the host), from the
program's ``serve.submit.featurize`` spans."""
import program_spans as ps


def read(ctx):
    return ps.mean_ms(ps.window_records(ctx), "serve.submit.featurize")
