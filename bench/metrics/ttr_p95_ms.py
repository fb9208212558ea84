"""95th percentile of time-to-result over every session completed in the
window: from the client's submit call to the collect of its result."""

from bench.harness import readers


def read(ctx):
    return readers.percentile_ms(ctx, "ttr_s", 95.0)
