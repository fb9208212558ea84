"""Deterministic latency model + congestion simulator (paper §IV, Fig 5).

The multi-chip fabric has *deterministic delays by design* (which is why
timestamps can be dropped on the wire).  Total chip-to-chip latency is a sum
of fixed per-stage terms plus a congestion-dependent queueing delay at the
Aggregator multiplexer and at the receiver's layer-2 link:

  chip→chip = L2_up + node_logic + MGT + agg_logic(+queue) + MGT
              + node_logic + L2_down(+queue) + on_chip

Calibration (paper §IV):
  * the two MGT hops take 0.3 µs;
  * ≈60 % of the remaining inter-FPGA delay is clock-domain-crossing counter
    synchronization, the rest packing logic, LUT pipeline stages and
    multiplexer arbitration;
  * total chip-to-chip latency stays within 0.9–1.3 µs for all spike rates;
  * measurement discretization is the 8 ns system clock;
  * worst-regime total jitter ≈15 % of the median delay.

The simulator is a vectorized discrete-event model (Lindley recursion over
merged arrivals) — pure JAX, used by ``benchmarks/fig5_latency.py``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.link import (LinkConfig, LINK_LATENCY_OPTIMIZED,
                             MGT_USER_CLOCK_HZ, cc_interval_words)

SYSTEM_CLOCK_NS = 8.0    # 125 MHz FPGA system clock
MGT_CLOCK_NS = 4.0       # 250 MHz transceiver user clock


@dataclasses.dataclass(frozen=True)
class LatencyParams:
    """Fixed per-stage latencies (ns), calibrated to §IV."""

    link: LinkConfig = LINK_LATENCY_OPTIMIZED
    # ASIC ↔ Node-FPGA layer-2 link (source-synchronous LVDS), each direction.
    l2_link_ns: float = 190.0
    # On-chip layer-1 crossbar traversal (runs at ASIC speed).
    on_chip_ns: float = 45.0
    # Clock-domain-crossing counter synchronizations, per FPGA traversal.
    # Three FPGAs are traversed; CDC is ~60 % of the non-MGT inter-FPGA delay.
    cdc_ns_per_fpga: float = 45.0
    # Packing/unpacking logic + address-LUT pipeline stages, per endpoint FPGA.
    pack_lut_ns: float = 36.0
    # Aggregator multiplexer arbitration (uncongested).
    mux_arb_ns: float = 18.0
    # Number of FPGAs traversed node→aggregator→node.
    n_fpgas: int = 3
    # Transceiver clock-compensation pauses: every ``cc_interval`` events the
    # datapath stalls for ``cc_stall_ns`` (§III "with the exception of
    # clock-compensation pauses").  Near link saturation these stalls are the
    # dominant source of queueing jitter.  The interval derives from the
    # transceiver ppm budget in ``repro.core.link.cc_interval_words`` — the
    # single source of truth shared with the bandwidth model.
    cc_interval: int = cc_interval_words()
    cc_stall_ns: float = 8.0

    # ---- fixed path sums ----------------------------------------------------
    def mgt_path_ns(self) -> float:
        """Both MGT hops (node→agg, agg→node)."""
        return 2.0 * self.link.hop_latency_ns()

    def fpga_to_fpga_ns(self) -> float:
        """Deterministic Node-FPGA → Node-FPGA latency (Fig 5A bottom)."""
        return (self.mgt_path_ns()
                + self.n_fpgas * self.cdc_ns_per_fpga
                + 2 * self.pack_lut_ns
                + self.mux_arb_ns)

    def chip_to_chip_ns(self) -> float:
        """Deterministic BSS-2 → BSS-2 latency (Fig 5A top), uncongested."""
        return self.fpga_to_fpga_ns() + 2 * self.l2_link_ns + self.on_chip_ns

    def second_layer_extra_ns(self) -> float:
        """Extra latency crossing the envisioned second-layer node (§V):
        two additional transceiver hops + one more aggregator traversal."""
        return (2.0 * self.link.hop_latency_ns()
                + self.cdc_ns_per_fpga + self.mux_arb_ns + self.pack_lut_ns)

    # ---- per-direction fixed paths (shared by the congestion simulator and
    # ---- the timed streaming datapath; see ``timed_wire``) -----------------
    def sender_fixed_ns(self, level: str = "chip") -> float:
        """Deterministic sender-side path up to the Aggregator multiplexer
        input: chip egress (chip level only) → Node-FPGA pack/LUT logic →
        CDC → MGT uplink hop."""
        fpga = (self.pack_lut_ns + self.cdc_ns_per_fpga
                + self.link.hop_latency_ns())
        if level == "chip":
            return self.on_chip_ns + self.l2_link_ns + fpga
        return fpga

    def recv_fixed_ns(self, level: str = "chip") -> float:
        """Deterministic receiver-side path from the multiplexer output to
        the destination: arbitration → MGT downlink hop → Node-FPGA
        unpack/LUT + CDC → layer-2 downlink (chip level only)."""
        fpga = (self.mux_arb_ns + self.link.hop_latency_ns()
                + self.pack_lut_ns + self.cdc_ns_per_fpga)
        if level == "chip":
            return (fpga + self.cdc_ns_per_fpga * (self.n_fpgas - 2)
                    + self.l2_link_ns)
        return fpga


DEFAULT_PARAMS = LatencyParams()

# Paper §IV headline claims (Fig 5): chip-to-chip median band across all
# spike rates, measurement discretization, and worst-regime total jitter.
PAPER_BAND_NS = (850.0, 1300.0)
PAPER_JITTER_FRAC = 0.15


# ---------------------------------------------------------------------------
# Per-hop queueing terms (vectorized; the timed datapath's delay model)
# ---------------------------------------------------------------------------


def queue_wait_ns(ranks, service_ns: float = MGT_CLOCK_NS, *,
                  cc_interval: int = 0, cc_stall_ns: float = 0.0) -> jax.Array:
    """Closed form of the Lindley recursion for one exchange window.

    When every event of a window arrives at the server together (the
    frame-synchronous streaming model), the waiting time of the event with
    0-based arrival rank ``r`` is the cumulative service of its predecessors:

        w_r = r · service + ⌊r / cc_interval⌋ · cc_stall

    (each ``cc_interval``-th predecessor carries one clock-compensation
    pause).  This is exactly ``_lindley_queue`` evaluated on simultaneous
    arrivals — pinned by ``tests/test_latency_model.py``.  Vectorized over
    any shape of integer ``ranks``.
    """
    r = jnp.asarray(ranks, jnp.int32)
    wait = r.astype(jnp.float32) * jnp.float32(service_ns)
    if cc_interval:
        wait = wait + (r // cc_interval).astype(jnp.float32) * jnp.float32(
            cc_stall_ns)
    return wait


class HopDelays(NamedTuple):
    """Per-event queueing delays (ns) at the congested hops of one window.

    Each field is the Lindley waiting time an event with the given 0-based
    arrival rank experiences at that hop; pass the sender-lane ranks to read
    ``uplink_ns`` and the destination merge-stream ranks for ``mux_ns`` /
    ``l2_down_ns``.
    """

    # Sender MGT lane: the Node-FPGA serializes its egress one word per
    # user-clock cycle, with clock-compensation pauses.
    uplink_ns: jax.Array
    # Aggregator multiplexer: all enabled sources merge into one stream.
    mux_ns: jax.Array
    # Receiver layer-2 downlink: runs at the mux output rate, so only its
    # own clock-compensation pauses add wait on top of the mux queue.
    l2_down_ns: jax.Array

    @property
    def total_ns(self) -> jax.Array:
        """Destination-side queueing (mux + layer-2 downlink)."""
        return self.mux_ns + self.l2_down_ns


def hop_delays(params: LatencyParams, occupancy) -> HopDelays:
    """Vectorized per-hop queueing terms for given arrival ranks.

    ``occupancy`` is an integer array of 0-based arrival ranks within one
    exchange window (how many events precede this one at the hop's server).
    Deterministic — the property the hardware exploits to drop timestamps on
    the wire — and exactly the congestion terms ``simulate_fan_in`` samples
    end-to-end.
    """
    r = jnp.asarray(occupancy, jnp.int32)
    serial = queue_wait_ns(r, MGT_CLOCK_NS, cc_interval=params.cc_interval,
                           cc_stall_ns=params.cc_stall_ns)
    stalls_only = queue_wait_ns(r, 0.0, cc_interval=params.cc_interval,
                                cc_stall_ns=params.cc_stall_ns)
    return HopDelays(uplink_ns=serial, mux_ns=serial, l2_down_ns=stalls_only)


def queue_wait_i32(ranks: jax.Array,
                   queue: tuple[int, int, int]) -> jax.Array:
    """Integer twin of ``queue_wait_ns`` for the int32 timestamp lane:
    rank·service + ⌊rank/cc⌋·stall, all int32.  ``queue`` is a static
    (service_ns, cc_interval, stall_ns) triple (``TimedWire.queue`` /
    ``TimedWire.uplink_queue``).  The single definition shared by the
    aggregator's uplink waits and the merge kernels' destination queue, so
    oracle and kernel timestamps cannot drift."""
    service_ns, cc_interval, stall_ns = queue
    wait = jnp.asarray(ranks, jnp.int32) * service_ns
    if cc_interval:
        wait = wait + (ranks // cc_interval) * stall_ns
    return wait


class TimedWire(NamedTuple):
    """Integer-ns constants of the timed streaming datapath.

    The timed exchange carries an int32 timestamp lane; all per-stage terms
    are therefore rounded to whole nanoseconds once, here, so the jnp oracle
    and the Pallas kernels add bit-identical delays.  ``queue`` is the
    static (service, cc_interval, stall_total) triple the merge-pack kernels
    fold into the destination pack rank.
    """

    sender_fixed_ns: int        # egress → Aggregator multiplexer input
    recv_fixed_ns: int          # multiplexer output → destination
    second_layer_extra_ns: int  # extra fixed path for inter-backplane events
    service_ns: int             # MGT user-clock cycle (one event per cycle)
    cc_interval: int            # events between clock-compensation pauses
    cc_stall_ns: int            # one compensation pause
    n_stall_hops: int           # stall-paying hops after the merge (mux + L2)

    @property
    def queue(self) -> tuple[int, int, int]:
        """(service_ns, cc_interval, stall_total_ns) for the merge kernels:
        the destination-side wait of pack rank r is
        r·service + ⌊r/cc⌋·stall_total — ``hop_delays(...).total_ns``."""
        return (self.service_ns, self.cc_interval,
                self.cc_stall_ns * self.n_stall_hops)

    @property
    def uplink_queue(self) -> tuple[int, int, int]:
        """(service_ns, cc_interval, stall_ns) of one sender-side lane."""
        return (self.service_ns, self.cc_interval, self.cc_stall_ns)


def timed_wire(params: LatencyParams = DEFAULT_PARAMS,
               level: str = "chip") -> TimedWire:
    """Integer-ns view of ``params`` for the timed exchange datapath.

    At zero congestion (rank 0 everywhere) the end-to-end delay is exactly
    ``sender_fixed_ns + recv_fixed_ns`` — ``chip_to_chip_ns`` at chip level
    — the closed-form property pinned by the latency test battery.
    """
    if level not in ("chip", "fpga"):
        raise ValueError(f"unknown level: {level!r}")
    return TimedWire(
        sender_fixed_ns=int(round(params.sender_fixed_ns(level))),
        recv_fixed_ns=int(round(params.recv_fixed_ns(level))),
        second_layer_extra_ns=int(round(params.second_layer_extra_ns())),
        service_ns=int(round(MGT_CLOCK_NS)),
        cc_interval=int(params.cc_interval),
        cc_stall_ns=int(round(params.cc_stall_ns)),
        # The layer-2 downlink only exists at chip level (Fig 5A top).
        n_stall_hops=2 if level == "chip" else 1,
    )


# ---------------------------------------------------------------------------
# Congestion simulator (Fig 5A)
# ---------------------------------------------------------------------------


def _lindley_queue(arrivals: jax.Array, service_ns,
                   cc_interval: int = 0, cc_stall_ns: float = 0.0) -> jax.Array:
    """Waiting time of each event at a single FIFO server.

    ``arrivals`` must be sorted ascending.  w_0 = 0;
    w_i = max(0, w_{i-1} + s_{i-1} - (a_i - a_{i-1})).

    ``cc_interval``/``cc_stall_ns`` model the transceiver's periodic
    clock-compensation pauses as extra service time on every Nth event.
    """
    arrivals = jnp.asarray(arrivals, jnp.float32)   # ns; ints promote here
    n = arrivals.shape[0]
    service = jnp.full((n,), service_ns, jnp.float32)
    if cc_interval:
        idx = jnp.arange(n)
        service = service + jnp.where(idx % cc_interval == cc_interval - 1,
                                      jnp.float32(cc_stall_ns), 0.0)
    gaps = jnp.diff(arrivals)

    def step(w_prev, inputs):
        gap, s = inputs
        w = jnp.maximum(0.0, w_prev + s - gap)
        return w, w

    _, waits = jax.lax.scan(step, jnp.float32(0.0), (gaps, service[:-1]))
    return jnp.concatenate([jnp.zeros((1,), waits.dtype), waits])


def simulate_fan_in(rate_hz: float,
                    n_spikes: int,
                    key: jax.Array,
                    fan_in: int = 3,
                    params: LatencyParams = DEFAULT_PARAMS,
                    level: str = "chip") -> jax.Array:
    """Simulate Fig 5A: ``fan_in`` regular senders → one receiver.

    Args:
      rate_hz: per-sender regular spike rate.
      n_spikes: total number of measured spikes (paper: 2^15).
      key: PRNG key for sender phase offsets + CDC alignment jitter.
      fan_in: number of senders (paper: 3).
      params: stage latencies.
      level: "fpga" (Node-FPGA → Node-FPGA) or "chip" (BSS-2 → BSS-2).

    Returns:
      float32[n_spikes] per-spike latencies in ns, quantized to the 8 ns
      measurement clock.
    """
    per_sender = -(-n_spikes // fan_in)
    k_phase, k_cdc, k_l2 = jax.random.split(key, 3)

    # Regular trains with uniform phase offsets (senders share the reference
    # clock but start at arbitrary alignment within one period).
    period_ns = 1e9 / rate_hz
    offsets = jax.random.uniform(k_phase, (fan_in,), minval=0.0,
                                 maxval=period_ns)
    idx = jnp.arange(per_sender, dtype=jnp.float32)
    emit = offsets[:, None] + idx[None, :] * period_ns      # [fan_in, per_sender]
    emit = emit.reshape(-1)[:n_spikes]

    # Fixed sender-side path up to the Aggregator multiplexer input.
    sender_fixed = params.sender_fixed_ns(level)

    # CDC alignment jitter: each crossing aligns to the destination clock —
    # uniform within one period per crossing (system + MGT domains).
    n_cross = 4 if level == "fpga" else 6
    jitter = jnp.zeros_like(emit)
    keys = jax.random.split(k_cdc, n_cross)
    for i in range(n_cross):
        period = SYSTEM_CLOCK_NS if i % 2 == 0 else MGT_CLOCK_NS
        jitter = jitter + jax.random.uniform(keys[i], emit.shape, maxval=period)

    arrive_mux = emit + sender_fixed + jitter

    # Aggregator multiplexer: one event per MGT user-clock cycle, with
    # periodic clock-compensation stalls.
    order = jnp.argsort(arrive_mux)
    sorted_arrivals = arrive_mux[order]
    mux_wait = _lindley_queue(sorted_arrivals, MGT_CLOCK_NS,
                              params.cc_interval, params.cc_stall_ns)

    # Receiver-side fixed path from multiplexer output to destination.
    recv_fixed = params.recv_fixed_ns(level)

    if level == "chip":
        # Receiver layer-2 link: sustains the ASIC's maximum spike rate — one
        # event per MGT cycle (§III) — with its own compensation stalls.
        depart_mux = sorted_arrivals + mux_wait + params.mux_arb_ns
        l2_wait = _lindley_queue(depart_mux, MGT_CLOCK_NS,
                                 params.cc_interval, params.cc_stall_ns)
        total_sorted = mux_wait + l2_wait
    else:
        total_sorted = mux_wait

    # Undo the sort so latencies align with emission order.
    inv = jnp.argsort(order)
    queue_wait = total_sorted[inv]

    latency = sender_fixed + jitter + queue_wait + recv_fixed
    if level == "chip":
        # Jitter compensation: delay events whose accumulated non-deterministic
        # delay is below the expected-link-delay target (lower-tail squashing).
        nondet = jitter + queue_wait
        comp_target = jnp.percentile(nondet, 30.0)
        comp_window_ns = 2.0 * SYSTEM_CLOCK_NS
        boost = jnp.clip(comp_target - nondet, 0.0, comp_window_ns)
        # Compensation only effective while the link is uncongested.
        congested = jnp.mean(queue_wait) > SYSTEM_CLOCK_NS
        latency = latency + jnp.where(congested, 0.0, boost)

    # Quantize to the 8 ns measurement clock (Fig 5 histogram discretization).
    return jnp.round(latency / SYSTEM_CLOCK_NS) * SYSTEM_CLOCK_NS


def latency_statistics(latencies_ns: jax.Array) -> dict[str, jax.Array]:
    med = jnp.median(latencies_ns)
    return {
        "median_ns": med,
        "p01_ns": jnp.percentile(latencies_ns, 1.0),
        "p99_ns": jnp.percentile(latencies_ns, 99.0),
        "jitter_ns": jnp.percentile(latencies_ns, 99.0)
                     - jnp.percentile(latencies_ns, 1.0),
        "jitter_frac": (jnp.percentile(latencies_ns, 99.0)
                        - jnp.percentile(latencies_ns, 1.0)) / med,
    }


# ---------------------------------------------------------------------------
# Fig 5B: speed-up factor vs routing latency in biological time
# ---------------------------------------------------------------------------


def biological_latency_ms(speedup: jax.Array,
                          hw_latency_ns: float | None = None) -> jax.Array:
    """Routing latency expressed in biological time for a given speed-up."""
    if hw_latency_ns is None:
        hw_latency_ns = DEFAULT_PARAMS.chip_to_chip_ns()
    return jnp.asarray(speedup) * hw_latency_ns * 1e-6  # ns → ms

# Typical biological membrane time constants (Allen atlas / NeuroElectro).
TAU_MEM_BIO_MS = (10.0, 30.0)
DEFAULT_SPEEDUP = 1000.0
