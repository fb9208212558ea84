"""Device time of the jitted stream program per emulated step, ms."""

from bench.harness import readers


def read(ctx):
    return readers.program_ms_per_step(ctx)
