"""Slot-steps that advanced a session, % of the slot-steps the window
program ran (``live`` over ``slot_steps`` of the ``engine.step`` spans)."""

from bench.harness import layers


def read(ctx):
    return layers.slot_occupancy(ctx)
