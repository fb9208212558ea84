"""Host time of one engine step() call, ms: the bench.engine.step span
minus the device time of the window program inside it."""

from bench.harness import readers


def read(ctx):
    return readers.host_ms_per_span(ctx, "bench.engine.step")
