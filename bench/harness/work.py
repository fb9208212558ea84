"""Semantic work of one emulated step, counted from the configuration's
shapes: what the emulation needs to read and write, whatever implements it.

Per live batch row (an unmasked engine slot, or a stream batch element):

* weights: six-bit weights are one byte per synapse.  Shared weights are
  read once per step for all rows; per-slot (plastic) weights are read and
  written once per live row;
* neuron state: the LIF state (v, i_syn, w_adapt, refrac: 16 B per neuron),
  read and written;
* delay-line slot: n_chips x n_rows x 4 B, read and written;
* plasticity traces (plastic): n_chips x (n_rows + n_neurons) x 4 B, read
  and written;
* event frames: every frame slot of every level (egress frame, leaf lane,
  each level's merge input and uplink, ingress frame) x (2 B wire word +
  4 B timestamp when timed), written and read.

The compute bound is the contraction in int8: 2 operations per synapse per
live row.  Because the count is by semantics, storing weights as int8 or
moving the pack out of Pallas does not make it stale.
"""

from __future__ import annotations

import math


def frame_slots(cfg: dict) -> int:
    """Event slots of one exchange round over all levels."""
    fab = cfg["fabric"]
    fan_ins, caps = fab["fan_ins"], fab["link_capacities"]
    n = math.prod(fan_ins)
    slots = n * fab["capacity"]
    length = fab["capacity"]
    if caps[0] is not None:
        slots += n * caps[0]
        length = caps[0]
    g = 1
    for i, f in enumerate(fan_ins):
        slots += n * f * length                    # each destination's part
        if i + 1 < len(fan_ins):
            n_grp = n // (g * f)
            length = caps[i + 1] if caps[i + 1] is not None else f * length
            slots += n_grp * length                # the uplinked streams
            g *= f
    return slots + n * fab["capacity"]


def step_work(cfg: dict, *, live_rows: float, plastic: bool,
              timed: bool) -> dict:
    """Bytes and int8 operations of one emulated step with ``live_rows``
    live batch rows.  Returns the parts and their totals."""
    n = math.prod(cfg["fabric"]["fan_ins"])
    R, N = cfg["chip"]["n_rows"], cfg["chip"]["n_neurons"]
    synapses = n * R * N
    parts = {
        "weights": (2 * live_rows * synapses) if plastic else synapses,
        "neuron_state": 2 * live_rows * n * N * 16,
        "delay_line": 2 * live_rows * n * R * 4,
        "traces": (2 * live_rows * n * (R + N) * 4) if plastic else 0,
        "event_frames": 2 * live_rows * frame_slots(cfg) * (2 + 4 * timed),
    }
    return {"parts": parts, "bytes": sum(parts.values()),
            "int8_ops": 2 * live_rows * synapses}


def roofline(work_bytes: float, ops: float, device_s: float,
             peak: dict) -> tuple[float, str]:
    """Share (%) of the least time the chip could take, and which bound
    sets that time ("hbm" or "int8")."""
    t_mem = work_bytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["int8_ops_per_s"]
    bound = "hbm" if t_mem >= t_ops else "int8"
    return 100.0 * max(t_mem, t_ops) / device_s, bound
