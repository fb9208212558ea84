"""Own device time of the ops of the stream program under the scope
``fabric/merge`` (destination merge, pack kernel, reverse LUT) per emulated
step, ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "fabric/merge")
