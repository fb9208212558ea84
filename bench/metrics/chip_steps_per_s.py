"""Emulated chip time-steps (chips x batch x steps) per second of window,
the window ending when the last call's outputs are ready (host clock)."""

from bench.harness import readers


def read(ctx):
    return readers.rate(ctx, "chip_steps")
