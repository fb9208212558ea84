"""Sessions completed inside the window per second of window (host clock)."""

from bench.harness import readers


def read(ctx):
    if ctx["traffic"]["path"] != "engine":
        return None
    return readers.rate(ctx, "completed")
