"""Own device time of the ops of the engine's window program under the scope
``fabric/leaf`` (forward LUT and leaf uplink pack) per emulated step, ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "fabric/leaf")
