"""Self time of the engine's ``engine.account`` and ``engine.finalize``
host spans (spike accounting and finished sessions' results) per
``engine.step`` span, ms."""

from bench.harness import layers


def read(ctx):
    return layers.span_ms_per_step(ctx, ("engine.account",
                                         "engine.finalize"))
