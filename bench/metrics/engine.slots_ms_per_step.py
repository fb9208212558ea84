"""Own device time of the ops of the engine's window program under the scope
``slots`` (reset select, stimulus slice, drive scatter) per emulated step,
ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "slots")
