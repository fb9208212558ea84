"""Self time of the engine's ``engine.readback`` host spans (device to
host copies) per ``engine.step`` span, ms."""

from bench.harness import layers


def read(ctx):
    return layers.span_ms_per_step(ctx, ("engine.readback",))
