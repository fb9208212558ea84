"""Own device time of the ops of the stream program under the scope ``ingress``
(label-to-row map and delay line) per emulated step, ms."""

from bench.harness import layers


def read(ctx):
    return layers.scope_ms_per_step(ctx, "ingress")
