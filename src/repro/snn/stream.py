"""Streaming multi-chip emulation engine — the time loop as one program.

The paper's system is *continuous-time*: spikes flow through the
Node-FPGA → Aggregator → Node-FPGA star every cycle, not one hand-dispatched
round at a time.  ``run_stream`` is the software analogue: the full
per-timestep pipeline —

    LIF/chip step → egress tap (label encode + capacity frame)
                  → fused exchange (star or two-layer hierarchical)
                  → delay-line ingress (chip-to-chip latency in steps)

— runs inside a single ``jax.lax.scan``, so a T-step emulation is one
compiled program instead of T dispatches.  Loop invariants are hoisted out
of the scan body: the egress label grid is built once, and the routing LUTs
enter the scan as closed-over constants (staged to device memory once per
stream, not per step).

The inter-chip delay line is kept as a ring buffer (``dynamic_index`` read +
``dynamic_update`` write of one slot per step) instead of the per-step
shift-concatenate of the eager path — for the common ``delay_steps == 2``
case this is literal double buffering: the frame written this step is the
frame consumed next step, with no copies of the in-flight buffer.  Outputs
and final state are bit-exact with the per-step path (the ring is rolled
back to shift order on exit).

Modes and topologies mirror ``repro.snn.network``:

* ``mode="event"``  — the faithful datapath through the N-level hop-graph
  executor (``repro.core.fabric``): the legacy ``"star"`` /
  ``"hierarchical"`` topologies compile to 1-/2-level plans, and arbitrary
  deeper topologies (extension-lane chains, §V and beyond) pass a compiled
  ``FabricPlan`` via ``fabric=``; fused or unfused.
* ``mode="dense"``  — the differentiable surrogate (routing matrices), so
  BPTT through ``run_stream`` is the training hot loop.

Each stage runs under a ``jax.named_scope`` — ``chip``, ``plasticity``,
``egress``, ``fabric`` (with ``leaf``, ``level<i>`` and ``merge`` inside) and
``ingress`` (row decode and delay line) — which the compiled program keeps
as each op's name, so a profiler trace splits the device time by stage.

The sharded twin (exchange scan under one ``shard_map``) is
``repro.core.aggregator.StarInterconnect.stream_fn``; the multi-step Pallas
kernel behind the fused exchange is ``repro.kernels.spike_router``.  Its
pack kernel also packs the chips' egress frames on the TPU
(``spike_router.ops.pack_frame``; ``events.make_frame`` off the TPU or with
``use_fused=False``).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fabric as fablib
from repro.core import latency as latlib
from repro.kernels.spike_router.ops import pack_frame
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import plasticity as plaslib


class StreamOut(NamedTuple):
    """Result of a streamed emulation run."""

    state: netlib.NetworkState
    spikes: jax.Array    # f32[T, n_chips, batch, n_neurons]
    dropped: jax.Array   # i32[T, n_chips, batch] egress + congestion drops
    #                      (zeros in dense mode)
    uplink_dropped: jax.Array  # i32[T, n_chips, batch] compact-before-gather
    #                      drops (nonzero only with link/pod capacities set)
    # Timed mode only (zero-width otherwise): per-event chip-to-chip wire
    # latency of every delivered ingress event, in ns — departure at the
    # window open, arrival = fixed per-stage path + deterministic queueing
    # (see ``core.latency.timed_wire``).  ``latency_valid`` masks the filled
    # ingress slots; padding slots carry 0.
    latency_ns: jax.Array      # i32[T, n_chips, batch, capacity | 0]
    latency_valid: jax.Array   # bool[T, n_chips, batch, capacity | 0]
    # Degraded-mode accounting (zeros on a healthy fabric / in dense mode):
    # per-step events lost to dead edges with no surviving route, and events
    # delivered over an extension-lane detour (``ExchangeDrops`` attribution
    # — subtree leaves for uplinks, destinations for downlinks).
    unroutable: jax.Array      # i32[T, n_chips, batch]
    rerouted: jax.Array        # i32[T, n_chips, batch]
    # Online-plasticity mode only (``plasticity=STDPConfig(...)``): the final
    # trace filters + evolved weights after the last step — irreplaceable
    # stream state (the chips' weights at step t exist nowhere else), part of
    # the checkpointable tree in ``runtime.elastic``.  ``None`` when the run
    # is non-plastic; a ``SlotPlasticityState`` (per-slot weights) when the
    # run was seeded with one (multi-tenant engine mode).
    plasticity: ("plaslib.StreamPlasticityState | "
                 "plaslib.SlotPlasticityState | None") = None


_LATENCY_STAT_KEYS = ("median_ns", "p01_ns", "p99_ns", "jitter_ns",
                      "jitter_frac")


def masked_latency_stats(latency_ns, latency_valid, *,
                         strict: bool = True) -> dict[str, float]:
    """Percentile summary of the valid-masked latency samples plus a
    ``count`` key.  Zero delivered events raises under ``strict`` (the
    historical behaviour — an untimed run or a dead stream is a caller
    bug); ``strict=False`` returns NaN-valued stats with ``count == 0``
    instead, so per-tenant accounting of idle sessions stays total.

    Computed on the host in float64 with NumPy (linear-interpolated
    percentiles at 1, 50 and 99): a sample array has a new length in
    almost every session, and an eager device reduction would compile
    again for each.  Device arrays are copied to the host first;
    ``core.latency.latency_statistics`` is the form for jitted callers."""
    lats = np.asarray(latency_ns)[np.asarray(latency_valid, bool)]
    count = int(lats.size)
    if count == 0:
        if strict:
            raise ValueError("no delivered events (or run_stream ran "
                             "untimed — pass timed=True)")
        return {**{k: float("nan") for k in _LATENCY_STAT_KEYS}, "count": 0}
    p01, med, p99 = np.percentile(lats.astype(np.float64), [1.0, 50.0, 99.0])
    return {"median_ns": float(med), "p01_ns": float(p01),
            "p99_ns": float(p99), "jitter_ns": float(p99 - p01),
            "jitter_frac": float((p99 - p01) / med), "count": count}


def stream_latency_stats(out: StreamOut, *,
                         strict: bool = True) -> dict[str, float]:
    """Host-side percentile summary of a timed stream's wire latencies.

    Masks the padding slots and summarises the rest with
    ``masked_latency_stats`` (median / p01 / p99 / jitter), plus a
    ``count`` of delivered events.
    Call on concrete (non-traced) outputs.  ``strict=False`` returns
    NaN stats (``count == 0``) instead of raising when nothing was
    delivered — see ``masked_latency_stats``.
    """
    return masked_latency_stats(out.latency_ns, out.latency_valid,
                                strict=strict)


def _egress_label_grid(cfg: netlib.NetworkConfig) -> jax.Array:
    """Static per-chip label grid for the layer-2 egress tap, hoisted out of
    the scan body (labels are configuration, not data)."""
    neurons = jnp.arange(cfg.chip.n_neurons, dtype=jnp.int32)
    chips = jnp.arange(cfg.n_chips, dtype=jnp.int32) << netlib.NEURON_BITS
    return chips[:, None] + neurons[None, :]


def run_stream(params: netlib.NetworkParams, state: netlib.NetworkState,
               ext_drives: jax.Array, cfg: netlib.NetworkConfig, *,
               mode: str = "event",
               topology: str = "star",
               route_mats: jax.Array | None = None,
               n_pods: int = 1,
               intra_enables: jax.Array | None = None,
               inter_enables: jax.Array | None = None,
               use_fused: bool | None = None,
               link_capacity: int | None = None,
               pod_capacity: int | None = None,
               fabric: "fablib.FabricPlan | None" = None,
               timed: bool = False,
               overlap: bool = False,
               faults: "Sequence[fablib.FaultEvent] | None" = None,
               fault_mode: str = "mask",
               plasticity: "plaslib.STDPConfig | None" = None,
               plasticity_state: "plaslib.StreamPlasticityState | "
               "plaslib.SlotPlasticityState | None" = None,
               slot_mask: jax.Array | None = None) -> StreamOut:
    """Scan the full emulation pipeline over ``ext_drives``.

    Args:
      ext_drives: f32[T, n_chips, batch, n_rows] external input per step.
      mode: ``"event"`` (faithful datapath) or ``"dense"`` (differentiable
        surrogate; requires ``route_mats`` from ``routing_matrices``).
      topology: ``"star"`` (one backplane) or ``"hierarchical"`` (§V
        two-layer; requires ``n_pods`` / ``intra_enables`` /
        ``inter_enables``, event mode only — the dense surrogate encodes
        topology in ``route_mats``).  Both compile to 1-/2-level fabric
        plans internally; deeper topologies pass a plan via ``fabric``.
      use_fused: event mode only; forwarded to the exchange kernels, and
        governs the egress pack kernel alike.
      link_capacity / pod_capacity: hierarchical event mode only — the
        compact-before-gather uplink stages of
        ``route_step_hierarchical``; overflow lands in
        ``StreamOut.uplink_dropped``, not ``dropped``.
      fabric: a compiled ``repro.core.fabric.FabricPlan`` — the exchange
        runs the N-level hop-graph executor (event mode only; the plan's
        leaf count and ingress capacity must match ``cfg``, and it replaces
        the ad-hoc topology flags: ``topology`` must stay ``"star"`` and
        the hierarchical/uplink arguments unset).  Route enables come from
        the *plan's* levels, NOT from ``params.router.route_enables`` (only
        the router's LUTs are used) — a plan built without explicit enables
        is all-to-all per level, so gated routers must bake their gating
        into the spec (``star_spec(..., enables=...)``) or the reverse
        LUTs.  Per-level uplink overflow lands in
        ``StreamOut.uplink_dropped``.
      timed: event mode only — thread the int32 timestamp lane through the
        exchange (``core.latency.timed_wire(cfg.latency)``): every spike of
        a window departs at the window open, and every delivered ingress
        event reports its chip-to-chip wire latency (fixed per-stage path +
        deterministic queueing at the sender lane, pod uplink and the
        destination merge) in ``StreamOut.latency_ns``.  The functional
        observables (spikes, dropped, uplink_dropped, state) are bit-exact
        with the untimed run.

      overlap: event mode only, ``delay_steps >= 2`` — double-buffer the
        exchange window: iteration ``t`` of the scan runs chip step ``t``
        *alongside* the exchange of step ``t-1``'s spikes (the two are
        data-independent, so the compiler — and a real fabric's DMA engine —
        can overlap timestep ``t``'s compute with timestep ``t-1``'s wire
        traffic).  The delay-line ring keeps this bit-exact: ``routed(t-1)``
        lands in slot ``(t-1) % delay``, still ``delay - 1`` iterations
        before its read, and a post-scan epilogue flushes the last window.
        All observables (spikes, drops, latencies, final state) are
        bit-exact with ``overlap=False``.  Incompatible with ``faults``
        (the health schedule indexes the *current* step's exchange).
      faults: event mode only — a schedule of ``fabric.FaultEvent`` link
        faults injected into the stream (each edge dies at ``kill_step``
        and optionally restores).  The per-step rerouted / lost counts
        surface in ``StreamOut.rerouted`` / ``StreamOut.unroutable``.
      fault_mode: how the schedule degrades the datapath.  ``"mask"``
        (default) drives dynamic health masks through the scan — one
        compiled program, in-graph within-plan degradation, dead edges
        lose their traffic as unroutable (no reroute).  ``"reroute"``
        splits the run at the health-change boundaries
        (``fabric.fault_boundaries``) and *recompiles* the plan per
        constant-health segment, so dead uplinks detour through the spare
        extension lanes where a healthy sibling has budget; the segments
        chain bit-exactly (the carried state crosses untouched).

      plasticity: an ``snn.plasticity.STDPConfig`` switches on online
        plasticity (the PPUs' hybrid-plasticity loop, Pehle et al. 2022):
        every step, after the chip update, the pre-synaptic row drive and
        the output spikes update per-chip/per-batch STDP traces and rewrite
        the (shared-per-chip) weight arrays in-scan — the chips integrate
        the *evolving* weights from the next step on.  The traces + weights
        ride the scan carry and the final state is returned in
        ``StreamOut.plasticity``; chain windows by passing it back via
        ``plasticity_state`` (bit-exact with one long run).  Works in both
        modes and composes with ``timed`` / ``faults``.
      plasticity_state: initial ``StreamPlasticityState`` (defaults to
        fresh zero traces over ``params.chips.weights``); requires
        ``plasticity``.  Passing a ``plaslib.SlotPlasticityState`` instead
        switches to *per-slot* plasticity: every batch row integrates and
        rewrites its own weight copy (``chip_step_slots``) with no
        cross-batch reduction, so batch rows are fully independent tenant
        sessions — the multi-tenant engine's mode
        (``runtime.engine.EmulationEngine``).  Bit-exact with the shared
        path at ``batch == 1``.
      slot_mask: bool[T, batch], optional — the multi-tenant engine's idle
        / tail masking.  A masked ``(t, b)`` entry zeroes slot ``b``'s
        output spikes at step ``t`` *before* recording, egress and
        plasticity: the slot emits no events (so it contributes zero
        entries to every drop counter — sessions are per-batch-row and the
        exchange is vmapped over batch), and under per-slot plasticity its
        traces and weights are frozen.  Unmasked rows are bit-exact with an
        unmasked run.  Composes with every mode (timed / overlap / faults /
        plasticity).

    Returns:
      ``StreamOut(state, spikes, dropped, uplink_dropped, latency_ns,
      latency_valid, unroutable, rerouted, plasticity)`` — bit-exact with
      the equivalent per-step loop (``run_event_steps`` / ``step_dense``
      iterated); the latency planes are zero-width unless ``timed``.
    """
    if mode not in ("event", "dense"):
        raise ValueError(f"unknown mode: {mode!r}")
    if topology not in ("star", "hierarchical"):
        raise ValueError(f"unknown topology: {topology!r}")
    if mode == "dense" and route_mats is None:
        raise ValueError("dense mode requires route_mats")
    if mode == "dense" and topology == "hierarchical":
        raise ValueError("hierarchical topology is event-mode only; dense "
                         "routing encodes the topology in route_mats")
    if topology == "hierarchical" and (intra_enables is None
                                       or inter_enables is None):
        raise ValueError("hierarchical topology requires intra_enables and "
                         "inter_enables")
    if topology != "hierarchical" and (link_capacity is not None
                                       or pod_capacity is not None):
        raise ValueError("link_capacity/pod_capacity are uplink stages of "
                         "the hierarchical topology (the stacked star round "
                         "has none)")
    if timed and mode != "event":
        raise ValueError("timed streams require the event datapath (the "
                         "dense surrogate has no wire to time)")
    if fault_mode not in ("mask", "reroute"):
        raise ValueError(f"unknown fault_mode: {fault_mode!r}")
    if plasticity_state is not None and plasticity is None:
        raise ValueError("plasticity_state without plasticity — pass the "
                         "STDPConfig that should drive the update")
    if slot_mask is not None and slot_mask.shape != (ext_drives.shape[0],
                                                     ext_drives.shape[2]):
        raise ValueError(f"slot_mask must be bool[T, batch] = "
                         f"{(ext_drives.shape[0], ext_drives.shape[2])}, "
                         f"got {slot_mask.shape}")
    if faults is not None and mode != "event":
        raise ValueError("fault injection requires the event datapath (the "
                         "dense surrogate has no links to kill)")
    if overlap:
        if mode != "event":
            raise ValueError("overlap double-buffers the exchange window — "
                             "event mode only (dense routing is a matmul, "
                             "there is no wire phase to overlap)")
        if state.inflight.shape[0] < 2:
            raise ValueError("overlap needs delay_steps >= 2: with a "
                             "single-slot delay line the deferred write "
                             "would land after its own read")
        if faults is not None:
            raise ValueError("overlap defers each exchange one iteration, "
                             "which would skew the per-step fault/health "
                             "schedule — run faults without overlap")
    if fabric is not None:
        if mode != "event":
            raise ValueError("fabric plans run the event datapath only")
        if topology != "star":
            raise ValueError("fabric replaces the topology flag — pass the "
                             "plan alone (leave topology at its default)")
        if fabric.n_nodes != cfg.n_chips:
            raise ValueError(f"fabric plan wires {fabric.n_nodes} leaves "
                             f"but the network has {cfg.n_chips} chips")
        if fabric.capacity != cfg.capacity:
            raise ValueError(f"fabric plan ingress capacity "
                             f"{fabric.capacity} != cfg.capacity "
                             f"{cfg.capacity}")

    n_steps = ext_drives.shape[0]
    delay = state.inflight.shape[0]
    labels_grid = _egress_label_grid(cfg)
    timing = latlib.timed_wire(cfg.latency) if timed else None
    # Per-slot plasticity (multi-tenant engine): each batch row carries its
    # own weight copy — decided by the *type* of the initial state, so the
    # scan body is a static choice, not a traced one.
    per_slot = isinstance(plasticity_state, plaslib.SlotPlasticityState)

    # Every event-mode topology is one hop-graph plan executed by the same
    # N-level engine; the legacy star/hierarchical flags compile to 1-/2-level
    # plans here (route enables come from the router state / the arguments).
    if mode == "event":
        if fabric is not None:
            plan = fabric
        elif topology == "star":
            plan = fablib.compile_fabric(fablib.star_spec(
                cfg.n_chips, cfg.capacity,
                enables=params.router.route_enables))
        else:
            plan = fablib.compile_fabric(fablib.hierarchical_spec(
                n_pods=n_pods, per_pod=cfg.n_chips // n_pods,
                capacity=cfg.capacity, intra_enables=intra_enables,
                inter_enables=inter_enables, link_capacity=link_capacity,
                pod_capacity=pod_capacity))

    # The egress pack runs where the fabric's merges run: the Pallas pack
    # kernel on the TPU unless ``use_fused`` is off, ``make_frame`` elsewhere.
    egress_mode = fablib.kernel_mode(fablib.fused_exchange_enabled()
                                      if use_fused is None else use_fused)

    def event_route(spikes, plan_seg, health_t):
        """Egress tap over every (batch, chip) row at once → exchange →
        ingress decode, vmapped over batch."""
        with jax.named_scope("egress"):
            # Timed egress: all spikes of the window depart at its open
            # (time 0 on the int32 lane), so the ingress times *are* the
            # chip-to-chip wire latencies: the frame's zero times are the
            # departures, and no time lane rides the pack.  Rows are
            # batch-major, so the fabric's vmap below maps the leading axis.
            fired = jnp.swapaxes(spikes, 0, 1) > 0.5  # [batch, n_chips, n]
            all_frames, all_egress_drop = pack_frame(
                jnp.broadcast_to(labels_grid, fired.shape), fired,
                capacity=cfg.capacity, mode=egress_mode)

        def one_batch(frames, egress_drop):  # [n_chips, capacity], [n_chips]
            with jax.named_scope("fabric"):
                ingress, drops = fablib.fabric_route_step(
                    params.router, frames, plan_seg, use_fused=use_fused,
                    timing=timing, health=health_t)
            with jax.named_scope("ingress"):
                drives = jax.vmap(
                    lambda lab, val, rmap: chiplib.labels_to_rows(
                        lab[None], val[None], rmap, cfg.chip.n_rows)[0])(
                            ingress.labels, ingress.valid,
                            params.row_of_label)
                if timed:
                    lat, lat_valid = ingress.times, ingress.valid
                else:
                    lat = jnp.zeros((*ingress.valid.shape[:-1], 0),
                                    jnp.int32)
                    lat_valid = jnp.zeros(lat.shape, jnp.bool_)
                return (drives, egress_drop + drops.congestion,
                        drops.uplink, lat, lat_valid, drops.unroutable,
                        drops.rerouted)

        return jax.vmap(one_batch, out_axes=(1, 1, 1, 1, 1, 1, 1))(
            all_frames, all_egress_drop)

    def chip_phase(chips, drive, plast, mask_t):
        """Chip step (shared or per-slot weights) + slot masking + the
        plasticity update — common to both scan bodies.  ``mask_t`` zeroes
        masked slots' spikes *before* recording/egress/plasticity, so an
        idle slot emits no events and (under per-slot plasticity) freezes
        its traces and weights."""
        with jax.named_scope("chip"):
            if per_slot:
                new_chips, spikes = jax.vmap(
                    lambda p, s, d, w: chiplib.chip_step_slots(p, s, d, w,
                                                               cfg.chip))(
                        params.chips, chips, drive, plast.weights)
            else:
                # Plastic runs integrate the *evolving* weights from the
                # carry; non-plastic runs keep the static params (same
                # program as before — ``plast`` is an empty pytree then).
                chip_params = (params.chips if plast is None else
                               params.chips._replace(weights=plast.weights))
                new_chips, spikes = jax.vmap(
                    lambda p, s, d: chiplib.chip_step(p, s, d, cfg.chip))(
                        chip_params, chips, drive)
            if mask_t is not None:
                spikes = jnp.where(mask_t[None, :, None], spikes, 0.0)
        if plast is not None:
            with jax.named_scope("plasticity"):
                if per_slot:
                    plast = plaslib.stdp_slot_step(plast, drive, spikes,
                                                   plasticity, mask=mask_t)
                else:
                    plast = plaslib.stdp_stream_step(plast, drive, spikes,
                                                     plasticity)
        return new_chips, spikes, plast

    def make_body(plan_seg):
        """Scan body over ``(drive_t, health_t, mask_t)`` for one
        constant-plan segment (``health_t`` is ``None`` without a mask
        schedule; ``mask_t`` is ``None`` without ``slot_mask``)."""

        def body(carry, xs):
            drive_t, health_t, mask_t = xs
            chips, inflight, t, plast = carry
            with jax.named_scope("ingress"):
                slot = jax.lax.rem(t, delay)
                # Ingress: consume the delay-line slot written ``delay``
                # steps ago.
                drive = drive_t + jax.lax.dynamic_index_in_dim(
                    inflight, slot, 0, keepdims=False)
            new_chips, spikes, plast = chip_phase(chips, drive, plast, mask_t)
            if mode == "dense":
                routed = jnp.einsum("sbn,sdnr->dbr", spikes, route_mats)
                dropped = jnp.zeros(spikes.shape[:2], jnp.int32)
                uplink = unroutable = rerouted = dropped
                lat = jnp.zeros((*spikes.shape[:2], 0), jnp.int32)
                lat_valid = jnp.zeros(lat.shape, jnp.bool_)
            else:
                (routed, dropped, uplink, lat, lat_valid, unroutable,
                 rerouted) = event_route(spikes, plan_seg, health_t)
            # Egress: the consumed slot is exactly the one due ``delay``
            # steps out — overwrite it in place (double buffering, no shift
            # copy).
            with jax.named_scope("ingress"):
                inflight = jax.lax.dynamic_update_index_in_dim(
                    inflight, routed, slot, 0)
            return ((new_chips, inflight, t + 1, plast),
                    (spikes, dropped, uplink, lat, lat_valid, unroutable,
                     rerouted))

        return body

    def make_body_overlap(plan_seg):
        """Scan body with the exchange deferred one iteration (see
        ``overlap``): chip step ``t`` and the exchange of ``spikes(t-1)``
        share an iteration with no data dependence between them, so the
        scheduler can run the wire phase under the compute phase."""

        def body(carry, xs):
            drive_t, _, mask_t = xs
            chips, inflight, t, plast, prev_spikes = carry
            with jax.named_scope("ingress"):
                slot = jax.lax.rem(t, delay)
                drive = drive_t + jax.lax.dynamic_index_in_dim(
                    inflight, slot, 0, keepdims=False)
            new_chips, spikes, plast = chip_phase(chips, drive, plast, mask_t)
            # prev_spikes were masked at production, so the deferred
            # exchange of a masked slot's window is already empty.
            (routed, dropped, uplink, lat, lat_valid, unroutable,
             rerouted) = event_route(prev_spikes, plan_seg, None)
            # routed(t-1) lands in slot (t-1) % delay, read at step
            # t-1+delay — never this iteration's slot while delay >= 2.
            # The t == 0 dummy exchange (zero previous window) must not
            # clobber the caller's initial in-flight frame due at step
            # delay-1, hence the gate.
            with jax.named_scope("ingress"):
                prev_slot = jax.lax.rem(t + delay - 1, delay)
                written = jax.lax.dynamic_update_index_in_dim(
                    inflight, routed, prev_slot, 0)
                inflight = jnp.where(t > 0, written, inflight)
            return ((new_chips, inflight, t + 1, plast, spikes),
                    (spikes, dropped, uplink, lat, lat_valid, unroutable,
                     rerouted))

        return body

    # Fault schedule → constant-plan segments.  Mask mode scans dynamic
    # health masks through one program; reroute mode recompiles the plan at
    # each health-change boundary and chains the scans (the carried state —
    # chip states, delay line, step counter — crosses segments untouched, so
    # the chain is bit-exact with a single scan of the same per-step plans).
    sched = None
    if mode != "event":
        segments = [(0, n_steps, None)]
    elif faults and fault_mode == "reroute":
        starts = fablib.fault_boundaries(faults, n_steps)
        segments = []
        for k, s in enumerate(starts):
            end = starts[k + 1] if k + 1 < len(starts) else n_steps
            dead = fablib.dead_edges_at(faults, s)
            plan_seg = (fablib.compile_fabric(
                fablib.degrade_spec(plan.spec, dead)) if dead else plan)
            segments.append((s, end, plan_seg))
    else:
        if faults:
            sched = fablib.health_schedule(plan, faults, n_steps)
        segments = [(0, n_steps, plan)]

    plast0 = None
    if plasticity is not None:
        with jax.named_scope("plasticity"):
            plast0 = (plasticity_state if plasticity_state is not None
                      else plaslib.init_stream_stdp(params.chips.weights,
                                                    ext_drives.shape[2]))
    carry = (state.chips, state.inflight, jnp.int32(0), plast0)
    if overlap:
        carry = (*carry, jnp.zeros((cfg.n_chips, ext_drives.shape[2],
                                    cfg.chip.n_neurons), ext_drives.dtype))
    ys_parts = []
    for start, end, plan_seg in segments:
        h = (None if sched is None else
             jax.tree.map(lambda a: a[start:end], sched))
        m = None if slot_mask is None else slot_mask[start:end]
        body = (make_body_overlap if overlap else make_body)(plan_seg)
        carry, ys = jax.lax.scan(body, carry, (ext_drives[start:end], h, m))
        ys_parts.append(ys)
    if overlap:
        chips, inflight, _, plast_final, last_spikes = carry
    else:
        chips, inflight, _, plast_final = carry
    (spikes, dropped, uplink, lat, lat_valid, unroutable, rerouted) = (
        ys_parts[0] if len(ys_parts) == 1
        else jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *ys_parts))
    if overlap:
        # Epilogue: flush the deferred last window, then realign the stats
        # streams (scan row t carried the stats of step t-1; row 0 was the
        # zero dummy window).
        (routed, e_drop, e_up, e_lat, e_latv, e_unr, e_rer) = event_route(
            last_spikes, segments[-1][2], None)
        with jax.named_scope("ingress"):
            inflight = jax.lax.dynamic_update_index_in_dim(
                inflight, routed, (n_steps - 1) % delay, 0)

        def _shift(a, tail):
            return jnp.concatenate([a[1:], tail[None]], axis=0)

        dropped = _shift(dropped, e_drop)
        uplink = _shift(uplink, e_up)
        lat = _shift(lat, e_lat)
        lat_valid = _shift(lat_valid, e_latv)
        unroutable = _shift(unroutable, e_unr)
        rerouted = _shift(rerouted, e_rer)
    # Restore shift-register order so the final state is bit-exact with the
    # per-step path (slot ``t % delay`` was written last).
    if delay > 1 and n_steps % delay:
        with jax.named_scope("ingress"):
            inflight = jnp.roll(inflight, -(n_steps % delay), axis=0)
    return StreamOut(state=netlib.NetworkState(chips=chips,
                                               inflight=inflight),
                     spikes=spikes, dropped=dropped, uplink_dropped=uplink,
                     latency_ns=lat, latency_valid=lat_valid,
                     unroutable=unroutable, rerouted=rerouted,
                     plasticity=plast_final)
