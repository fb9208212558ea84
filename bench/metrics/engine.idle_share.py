"""Device idle share (%) of the traced engine window."""

from bench.harness import readers


def read(ctx):
    return readers.idle_share(ctx)
