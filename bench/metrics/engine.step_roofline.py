"""Share (%) of the window program's device time that the semantic bytes
and int8 operations of its emulated steps need at the chip's peaks."""

from bench.harness import readers


def read(ctx):
    return readers.step_roofline(ctx)
