"""What the driving paths in ``bench/drivers/`` share.

A driving path is a file ``bench/drivers/<path>.py`` with a class ``Cell``
that a traffic mix names by its ``path`` key.  A ``Cell(cfg, traffic,
seed)`` has:

* ``unit``: what the window completes ("session", "call");
* ``program``: after ``setup``, the name of the program's HLO module as
  the trace shows it;
* ``setup()``: builds the system and warms every shape the window uses;
* ``window(seconds)``: drives the traffic and returns the window's counts
  (``window_s``, ``completed``, ``failed``, ``program_calls``,
  ``emulated_steps``, ``live_row_steps`` and what its readers need), with
  optional ``notes``, lines printed before the result;
* ``release()``: frees the program's device state before the reference
  runs;
* ``check(control_mode=None)``: the comparison of a sample of what the
  window produced with the plain reference (``check.py``).

Host spans around the calls into the program are
``jax.profiler.TraceAnnotation``s named ``bench.*``; the trace reduction
attributes the device's idle gaps to them.
"""

from __future__ import annotations

import jax
import numpy as np

span = jax.profiler.TraceAnnotation

# Stimulus index of a warm-up session: never one the window draws.
WARM_INDEX = 2**40


class Reservoir:
    """A uniform sample of ``size`` items from a stream, drawn from the
    seed (reservoir sampling)."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.items = size, 0, []
        self.rng = np.random.default_rng([seed, 3])

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = item


def state_dict(state) -> dict:
    """Host copies of a ``NetworkState``'s neuron state and delay line."""
    nrn = state.chips.neurons
    return {"v": np.asarray(nrn.v), "i_syn": np.asarray(nrn.i_syn),
            "w_adapt": np.asarray(nrn.w_adapt),
            "refrac": np.asarray(nrn.refrac),
            "inflight": np.asarray(state.inflight)}
