"""Plain reference of the emulated BrainScaleS-2 multi-chip system.

Written from the configuration file alone: it imports nothing of the
program under test and uses no table, weight or constant the program made.

Two parts:

* ``FabricRef`` -- one exchange round of the hop-graph fabric in numpy,
  event by event in arrival order: the egress frame of every chip, the
  forward LUT (each chip's neurons onto its own block of wire labels, one
  per synapse row driven), the leaf lane, every level's uplink pack, the
  destination merge (nearest level first), the ingress pack, the reverse
  LUT and the synapse-row decode of the feed-forward wiring.  It returns the delivered row drive, the four loss
  counters and the timestamp lane.
* ``lif_scan`` -- the chips' LIF neurons, the 6-bit weights and the STDP
  update, driven by the program's own spikes (teacher forcing).  At every
  step it measures by how far the reference membrane lies on the wrong side
  of the threshold for the spike the program emitted: the ``spike_gap``.

Because the reference is forced with the program's spikes, one flipped
threshold decision does not cascade: the gap of a sound run stays at the
rounding level of the stated precision, and a run computed at a lower
precision, or with an altered spike, an altered delivery or a stale state,
shows a gap of the size of its error.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Constants from the configuration file
# ---------------------------------------------------------------------------


class Wire(NamedTuple):
    """Integer-ns constants of the timed wire (paper section IV)."""

    sender_ns: int       # chip egress -> first multiplexer input
    recv_ns: int         # last multiplexer output -> destination chip
    extra_ns: int        # each crossing above the backplane
    service_ns: int      # one event per transceiver user-clock cycle
    cc_interval: int     # events between clock-compensation pauses
    stall_ns: int        # one pause on one lane
    dest_stall_ns: int   # pauses of the multiplexer and the layer-2 downlink
    delay_steps: int     # chip-to-chip latency in whole emulation steps


def wire_constants(cfg: dict) -> Wire:
    lat = cfg["latency"]
    hop = lat["mgt_fixed_latency_ns"] + (
        max(lat["word_bits"] / lat["mgt_data_bits"], 1.0)
        * lat["mgt_code_bits"] / lat["mgt_line_rate_gbps"])
    cdc, l2, lut = lat["cdc_ns_per_fpga"], lat["l2_link_ns"], lat["pack_lut_ns"]
    mux, chip = lat["mux_arb_ns"], lat["on_chip_ns"]
    sender = chip + l2 + lut + cdc + hop
    recv = mux + hop + lut + cdc + cdc * (lat["n_fpgas"] - 2) + l2
    extra = 2 * hop + cdc + mux + lut
    chip_to_chip = 2 * hop + lat["n_fpgas"] * cdc + 2 * lut + mux + 2 * l2 + chip
    stall = int(round(lat["cc_stall_ns"]))
    return Wire(sender_ns=int(round(sender)), recv_ns=int(round(recv)),
                extra_ns=int(round(extra)), service_ns=int(lat["service_ns"]),
                cc_interval=int(lat["cc_interval"]), stall_ns=stall,
                dest_stall_ns=2 * stall,
                delay_steps=max(1, math.ceil(chip_to_chip
                                             / (lat["dt_us"] * 1000.0))))


def _wait(rank, service: int, cc: int, stall: int):
    """Queueing wait of the event with 0-based rank ``rank`` at one lane."""
    return rank * service + (rank // cc) * stall


def _pack(valid: np.ndarray, cap: int):
    """First ``cap`` valid slots of each row, in order.

    Returns (source column per output slot, -1 where empty; overflow count
    per row)."""
    rows, width = valid.shape
    rank = np.cumsum(valid, axis=-1) - 1
    keep = valid & (rank < cap)
    r, c = np.nonzero(keep)
    src = np.full((rows, cap), -1, np.int64)
    src[r, rank[r, c]] = c
    return src, np.maximum(valid.sum(axis=-1) - cap, 0)


def _take(a: np.ndarray, src: np.ndarray) -> np.ndarray:
    return np.where(src >= 0, np.take_along_axis(a, np.maximum(src, 0), -1), 0)


class RoundOut(NamedTuple):
    drive: np.ndarray        # f32[B, n, n_rows]    delivered synapse-row drive
    dropped: np.ndarray      # i64[B, n]            egress + congestion
    uplink: np.ndarray       # i64[B, n]            uplink overflow
    latency: np.ndarray      # i64[B, n, capacity]  arrival time, 0 if empty
    valid: np.ndarray        # bool[B, n, capacity]


class FabricRef:
    """One exchange round of the configured fabric, healthy links."""

    def __init__(self, cfg: dict):
        fab = cfg["fabric"]
        self.fan_ins = tuple(int(f) for f in fab["fan_ins"])
        self.n = math.prod(self.fan_ins)
        self.capacity = int(fab["capacity"])
        self.caps = tuple(None if c is None else int(c)
                          for c in fab["link_capacities"])
        self.n_rows = int(cfg["chip"]["n_rows"])
        self.n_neurons = int(cfg["chip"]["n_neurons"])
        self.neuron_bits = int(cfg["labels"]["neuron_bits"])
        # Each chip owns one wire label per synapse row its events drive.
        self.block = self.n_rows
        if self.n * self.block > 1 << int(cfg["labels"]["wire_label_bits"]):
            raise ValueError("the chips' wire label blocks overflow the "
                             "wire label space")
        self.wire = wire_constants(cfg)

    def route(self, spikes: np.ndarray) -> RoundOut:
        """``spikes``: bool[B, n, n_neurons] emitted this step."""
        w = self.wire
        B, n, N = spikes.shape
        leaf = np.arange(n)
        # Forward LUT: chip c sends neuron j as wire label c * block + j %
        # block (every neuron of every chip has an entry).
        labels = np.broadcast_to(
            leaf[:, None] * self.block + np.arange(N)[None] % self.block,
            (B, n, N)).reshape(B * n, N)

        # Egress frame of each chip: its first `capacity` spikes.
        src, egress_drop = _pack(spikes.reshape(B * n, N), self.capacity)
        lab = _take(labels, src)
        ev = src >= 0
        rank = np.cumsum(ev, axis=-1) - 1
        t = np.where(ev, w.sender_ns + _wait(rank, w.service_ns,
                                             w.cc_interval, w.stall_ns), 0)
        uplink = np.zeros((B, n), np.int64)
        if self.caps[0] is not None:
            src, drop = _pack(ev, self.caps[0])
            lab, t, ev = _take(lab, src), _take(t, src), src >= 0
            uplink += drop.reshape(B, n)
        W, T, V = (a.reshape(B, n, -1) for a in (lab, t, ev))

        parts = []
        g = 1                                   # leaves per entity
        for i, f in enumerate(self.fan_ins):
            L = W.shape[-1]
            n_grp = n // (g * f)
            SW, ST, SV = (a.reshape(B, n_grp, f * L) for a in (W, T, V))
            anc = leaf // (g * f)
            child = (leaf // g) % f
            # Every sibling entity but the destination's own.
            gate = np.repeat(np.arange(f)[None, :] != child[:, None], L, 1)
            parts.append((SW[:, anc], ST[:, anc], SV[:, anc] & gate[None]))
            if i + 1 < len(self.fan_ins):
                prank = np.cumsum(SV, axis=-1) - 1
                ST = np.where(SV, ST + w.extra_ns + _wait(
                    prank, w.service_ns, w.cc_interval, w.stall_ns), 0)
                cap = self.caps[i + 1]
                if cap is not None:
                    src, drop = _pack(SV.reshape(B * n_grp, f * L), cap)
                    flat = lambda a: a.reshape(B * n_grp, f * L)
                    W = _take(flat(SW), src).reshape(B, n_grp, cap)
                    T = _take(flat(ST), src).reshape(B, n_grp, cap)
                    V = (src >= 0).reshape(B, n_grp, cap)
                    uplink += drop.reshape(B, n_grp)[:, anc]
                else:
                    W, T, V = SW, ST, SV
                g *= f

        MW, MT, MV = (np.concatenate([p[k] for p in parts], axis=-1)
                      .reshape(B * n, -1) for k in range(3))
        src, congestion = _pack(MV, self.capacity)
        out_l = _take(MW, src)
        valid = src >= 0
        slot = np.arange(self.capacity)[None]
        out_t = np.where(valid, _take(MT, src) + w.recv_ns + _wait(
            slot, w.service_ns, w.cc_interval, w.dest_stall_ns), 0)

        # Reverse LUT (wire label c * block + r -> chip c, neuron r) and the
        # feed-forward row decode: chip d takes the events of chip d-1,
        # neuron j onto row j mod n_rows.
        src_chip, j = out_l // self.block, out_l % self.block
        dst = np.tile(leaf, B)[:, None]
        ok = valid & (src_chip == dst - 1) & (j < N)
        row = np.where(ok, j % self.n_rows, self.n_rows)
        flat_idx = (np.arange(B * n)[:, None] * (self.n_rows + 1) + row)
        drive = np.bincount(flat_idx.ravel(), weights=ok.ravel(),
                            minlength=B * n * (self.n_rows + 1))
        drive = drive.reshape(B, n, self.n_rows + 1)[..., :self.n_rows]
        return RoundOut(drive=drive.astype(np.float32),
                        dropped=(egress_drop + congestion).reshape(B, n),
                        uplink=uplink,
                        latency=out_t.reshape(B, n, self.capacity),
                        valid=valid.reshape(B, n, self.capacity))

    def stream(self, spikes: np.ndarray):
        """Route every step of ``spikes`` (bool[T, B, n, N]); returns the
        stacked ``RoundOut`` fields with a leading time axis."""
        outs = [self.route(s) for s in spikes]
        return RoundOut(*(np.stack(f) for f in zip(*outs)))


# ---------------------------------------------------------------------------
# Neurons, weights and plasticity, driven by the program's spikes
# ---------------------------------------------------------------------------

# The control of a stated precision is the nearest one below it.
CONTROL_OF = {"float32": "bfloat16", "bfloat16_operands": "float8_operands"}


def contraction_mode(cfg: dict, *, per_slot: bool) -> str:
    """The precision the configuration states for the contraction of
    per-slot weight copies (the plastic engine) or of the shared weights."""
    p = cfg["precision"]
    return p["per_slot_contraction" if per_slot else "shared_contraction"]


def _contract(drive, w_eff, mode: str):
    """Row contraction current[n, B, N] = sum_r drive[n, B, r] w[.., r, N]
    at the precision ``mode`` names."""
    eq = "cbr,crn->cbn" if w_eff.ndim == 3 else "cbr,cbrn->cbn"
    if mode == "float32":
        return jnp.einsum(eq, drive, w_eff,
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "float32_high":                  # three bfloat16 passes
        return jnp.einsum(eq, drive, w_eff, precision=jax.lax.Precision.HIGH)
    if mode == "bfloat16_operands":
        return jnp.einsum(eq, drive.astype(jnp.bfloat16),
                          w_eff.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "float8_operands":
        f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.einsum(eq, f8(drive), f8(w_eff),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "bfloat16":
        return jnp.einsum(eq, drive.astype(jnp.bfloat16),
                          w_eff.astype(jnp.bfloat16),
                          preferred_element_type=jnp.bfloat16)
    raise ValueError(f"unknown precision {mode!r}")


def state_dtype(mode: str):
    return jnp.bfloat16 if mode == "bfloat16" else jnp.float32


class LIFState(NamedTuple):
    v: jax.Array            # [n, B, N]
    i_syn: jax.Array
    w_adapt: jax.Array
    refrac: jax.Array       # int32
    trace_pre: jax.Array    # [n, B, R]   (zero-size when not plastic)
    trace_post: jax.Array   # [n, B, N]
    weights: jax.Array      # [n, R, N] shared or [n, B, R, N] per slot


def init_lif(cfg: dict, weights, batch: int, *, plastic: bool,
             mode: str = "float32") -> LIFState:
    """Fresh neurons at rest; per-slot weight copies when ``plastic``."""
    n = math.prod(cfg["fabric"]["fan_ins"])
    R, N = cfg["chip"]["n_rows"], cfg["chip"]["n_neurons"]
    dt = state_dtype(mode)
    z = jnp.zeros((n, batch, N), dt)
    w = jnp.asarray(weights, jnp.float32)
    if plastic:
        w = jnp.broadcast_to(w[:, None], (n, batch, R, N))
        tp = jnp.zeros((n, batch, R), dt)
        tq = jnp.zeros((n, batch, N), dt)
    else:
        tp = tq = jnp.zeros((0,), dt)
    return LIFState(v=z + cfg["neuron"]["v_leak"], i_syn=z, w_adapt=z,
                    refrac=jnp.zeros((n, batch, N), jnp.int32),
                    trace_pre=tp, trace_post=tq, weights=w.astype(dt))


def _lif_step(cfg: dict, mode: str, plastic: bool, row_sign, w_scale):
    nc, sc = cfg["neuron"], cfg.get("stdp")
    wmax = float((1 << cfg["chip"]["weight_bits"]) - 1)
    a_mem = math.exp(-nc["dt_us"] / nc["tau_mem_us"])
    a_syn = math.exp(-nc["dt_us"] / nc["tau_syn_us"])
    a_ad = math.exp(-nc["dt_us"] / nc["tau_adapt_us"])
    refrac_steps = int(round(nc["refrac_us"] / nc["dt_us"]))
    dt = state_dtype(mode)

    def step(s: LIFState, drive, spikes=None):
        """``spikes``: the program's decisions that reset the neurons
        (teacher forcing), or None for the reference's own."""
        drive = drive.astype(dt)
        w = jnp.round(jnp.clip(s.weights, 0.0, wmax))          # 6-bit weights
        scale, sign = w_scale.astype(dt), row_sign.astype(dt)
        if w.ndim == 3:
            w_eff = w * scale[:, None, None] * sign[:, :, None]
        else:
            w_eff = w * scale[:, None, None, None] * sign[:, None, :, None]
        current = _contract(drive, w_eff.astype(dt), mode).astype(dt)
        i_syn = a_syn * s.i_syn + current
        dv = (1.0 - a_mem) * (nc["v_leak"] - s.v)
        if nc["delta_t"] > 0.0:
            arg = jnp.clip((s.v - nc["v_exp"]) / nc["delta_t"], -20.0, 20.0)
            dv = dv + (1.0 - a_mem) * nc["delta_t"] * jnp.exp(arg)
        v = s.v + (dv + (1.0 - a_mem) * (i_syn - s.w_adapt))
        v = jnp.where(s.refrac > 0, nc["v_reset"], v)
        margin = v.astype(jnp.float32) - nc["v_th"]
        if spikes is None:
            spikes = v > nc["v_th"]
        # Teacher forcing: the program's spike resets the neuron.
        post = spikes.astype(dt)
        v_next = (1.0 - post) * v + post * nc["v_reset"]
        w_adapt = (a_ad * s.w_adapt
                   + (1.0 - a_ad) * nc["adapt_a"] * (s.v - nc["v_leak"])
                   + post * nc["adapt_b"])
        refrac = jnp.where(spikes, jnp.int32(refrac_steps),
                           jnp.maximum(s.refrac - 1, 0))
        tp, tq, weights = s.trace_pre, s.trace_post, s.weights
        if plastic:
            a_pre = math.exp(-sc["dt_us"] / sc["tau_pre_us"])
            a_post = math.exp(-sc["dt_us"] / sc["tau_post_us"])
            tp = a_pre * tp + drive
            tq = a_post * tq + post
            dw = (sc["lr_pot"] * (tp[..., :, None] * post[..., None, :])
                  - sc["lr_dep"] * (drive[..., :, None] * tq[..., None, :]))
            weights = jnp.clip(weights + dw * wmax, 0.0, wmax)
        return LIFState(v_next, i_syn, w_adapt, refrac, tp, tq,
                        weights), margin, v > nc["v_th"]

    return step


def free_step(cfg: dict, mode: str, plastic: bool):
    """One step of the reference on its own spikes:
    ``(row_sign, w_scale, state, drive f32[n, B, R]) -> (state, spikes)``."""

    def step(row_sign, w_scale, s: LIFState, drive):
        s, _, spikes = _lif_step(cfg, mode, plastic, row_sign, w_scale)(
            s, drive)
        return s, spikes

    return step


def _gap(margin, spikes):
    """How far the membrane lies on the wrong side of the threshold for the
    spike decision ``spikes`` (0 where they agree)."""
    return jnp.where(spikes, jnp.maximum(-margin, 0.0),
                     jnp.maximum(margin, 0.0))


def lif_scan(cfg: dict, row_sign, w_scale, state: LIFState, drives, spikes,
             live, *, mode: str, plastic: bool,
             control: LIFState | None = None, control_mode: str | None = None):
    """Teacher-forced neuron scan over T steps.

    drives: f32[T, n, B, R] total synapse-row drive of each step (external
    plus delivered); spikes: bool[T, n, B, N], the program's; live:
    bool[T] (steps past a session's end are padding).  Returns (final
    state, widest spike gap, final control state, widest gap of the
    control's own decisions measured on this reference's membrane).
    """
    ref_step = _lif_step(cfg, mode, plastic, row_sign, w_scale)
    ctl_step = (None if control is None else
                _lif_step(cfg, control_mode, plastic, row_sign, w_scale))

    def body(carry, xs):
        s, c = carry
        d, spk, ok = xs
        s2, margin, _ = ref_step(s, d, spk)
        gap = jnp.where(ok, _gap(margin, spk).max(), 0.0)
        cgap = jnp.float32(0.0)
        if ctl_step is not None:
            c2, _, decision = ctl_step(c, d, spk)
            cgap = jnp.where(ok, _gap(margin, decision).max(), 0.0)
            c = jax.tree.map(lambda new, old: jnp.where(ok, new, old), c2, c)
        s = jax.tree.map(lambda new, old: jnp.where(ok, new, old), s2, s)
        return (s, c), (gap, cgap)

    (s, c), (gaps, cgaps) = jax.lax.scan(body, (state, control),
                                         (drives, spikes, live))
    return s, gaps.max(), c, cgaps.max()
