"""Own device time of the ops of the stream program under the scope ``egress``
(the pack of each chip's spikes into its event frame) per emulated step, ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "egress")
