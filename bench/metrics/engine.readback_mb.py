"""Bytes the engine copies to the host (``bytes`` of its
``engine.readback`` spans) per ``engine.step`` span, MB."""

from bench.harness import layers


def read(ctx):
    return layers.readback_mb(ctx)
