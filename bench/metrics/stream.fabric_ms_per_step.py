"""Own device time of the ops of the stream program under the scope ``fabric``
(the whole exchange round: leaf, every level, merge) per emulated step, ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "fabric")
