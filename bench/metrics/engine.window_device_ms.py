"""Device time of one call of the engine's window program, ms."""

from bench.harness import readers


def read(ctx):
    return readers.program_ms_per_call(ctx)
