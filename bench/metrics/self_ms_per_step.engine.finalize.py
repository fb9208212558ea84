"""Self time of the engine's ``engine.finalize`` host spans (finished
sessions' results, less the readback and latency spans inside them) per
``engine.step`` span, ms: ``trace_layers.py``'s reading of that name."""

from bench.harness import layers


def read(ctx):
    return layers.span_ms_per_step(ctx, ("engine.finalize",))
