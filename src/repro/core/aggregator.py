"""The Aggregator: star-topology spike exchange (paper §III).

Hardware: every Node-FPGA forwards enabled spikes over its MGT lane to the
Aggregator, which broadcasts them all-to-all with static per-route enables;
receiving Node-FPGAs translate wire labels back to chip labels and inject.

TPU mapping: the mesh axis that spans the participating "chips" plays the
backplane; ``jax.lax.all_gather`` along that axis *is* the star broadcast
(one hop up, one hop down).  The envisioned second-layer node (§V) becomes a
second, outer mesh axis with its own gather — traffic crossing backplanes
pays the extra hops, exactly like the projected +0.4 µs.

Fabric datapath: since ISSUE 5 every entry point in this module is a thin
wrapper over ``repro.core.fabric`` — the star is a 1-level hop-graph plan,
the §V two-layer system a 2-level plan, both executed by the same generic
N-level engine (``fabric_route_step`` stacked, ``fabric_exchange`` under
``shard_map``).  Deeper topologies (e.g. cases chained over the Aggregator's
4 extension lanes) use ``fabric`` directly; these wrappers exist for
API stability and stay bit-exact with their pre-fabric implementations —
spikes, drops, pack order and the timed lane (pinned by the wrapper-parity
battery in ``tests/test_fabric.py`` and the golden fixture).

All paths agree on (labels·valid, valid, dropped); untimed exchange outputs
carry zeroed timestamps (the multi-chip extension discards them, §III) and
zero labels in invalid slots.  The sparsity-aware wire path (compact-before-
gather uplink capacities, segmented pack, 16-bit wire words) and the timed
timestamp lane are plan/executor features — see ``repro.core.fabric``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fabric as fablib
from repro.core import routing
from repro.core.events import EventFrame
from repro.core.fabric import (  # noqa: F401  (re-exported legacy API)
    ExchangeDrops, fused_exchange_enabled)
from repro.core.latency import TimedWire
from repro.core.link import LinkConfig


class RouterState(NamedTuple):
    """Static routing state of one backplane (stacked per-node tables)."""

    fwd_tables: jax.Array      # int32[n_nodes, 2^16]
    rev_tables: jax.Array      # int32[n_nodes, 2^15]
    route_enables: jax.Array   # bool[n_nodes, n_nodes]


def identity_router(n_nodes: int, route_enables: jax.Array | None = None,
                    n_labels: int | None = None) -> RouterState:
    tables = routing.identity_tables(n_labels)
    if route_enables is None:
        route_enables = routing.full_route_enables(n_nodes)
    return RouterState(
        fwd_tables=jnp.broadcast_to(tables.fwd, (n_nodes, tables.fwd.shape[0])),
        rev_tables=jnp.broadcast_to(tables.rev, (n_nodes, tables.rev.shape[0])),
        route_enables=route_enables,
    )


# ---------------------------------------------------------------------------
# Semantic reference: one device holds all nodes' frames
# ---------------------------------------------------------------------------


def route_step(state: RouterState, frames: EventFrame, capacity: int, *,
               use_fused: bool | None = None,
               timing: TimedWire | None = None
               ) -> tuple[EventFrame, jax.Array]:
    """Full datapath for one exchange round.

    .. deprecated:: prefer ``repro.core.fabric`` — this is a thin wrapper
       over the 1-level fabric plan (``fabric.star_spec`` +
       ``fabric.fabric_route_step``); arbitrary N-level topologies go
       through the fabric API directly.

    Args:
      state: backplane routing state.
      frames: per-node egress frames, arrays shaped [n_nodes, cap_in].
      capacity: ingress frame capacity per node.
      use_fused: route through the fused exchange kernel (default: the
        ``REPRO_FUSED_EXCHANGE`` env flag, on).
      timing: timed datapath (``latency.timed_wire``): ``frames.times`` are
        int32 departure timestamps (ns); the returned ingress ``times`` are
        per-event arrival timestamps — departure + fixed per-stage path +
        deterministic queueing at the sender lane and the destination merge.
        ``None`` (default) keeps the untimed wire: timestamps are discarded
        at egress (§III) and the ingress carries zeros.

    Returns:
      (ingress frames [n_nodes, capacity], dropped counts [n_nodes]).
      ``dropped`` is the plain congestion counter — the stacked single-star
      round has no uplink stage (see ``route_step_hierarchical`` /
      ``star_exchange`` for the ``ExchangeDrops``-returning paths).
    """
    plan = fablib.compile_fabric(fablib.star_spec(
        state.route_enables.shape[0], capacity,
        enables=state.route_enables))
    ingress, drops = fablib.fabric_route_step(state, frames, plan,
                                              use_fused=use_fused,
                                              timing=timing)
    return ingress, drops.congestion


def route_step_hierarchical(state: RouterState, frames: EventFrame,
                            capacity: int, *, n_pods: int,
                            intra_enables: jax.Array,
                            inter_enables: jax.Array,
                            use_fused: bool | None = None,
                            link_capacity: int | None = None,
                            pod_capacity: int | None = None,
                            timing: TimedWire | None = None
                            ) -> tuple[EventFrame, ExchangeDrops]:
    """One two-layer (§V) exchange round with all nodes stacked on one device.

    .. deprecated:: prefer ``repro.core.fabric`` — this is a thin wrapper
       over the 2-level fabric plan (``fabric.hierarchical_spec`` +
       ``fabric.fabric_route_step``); N-level topologies (extension-lane
       chains, deeper switched fabrics) go through the fabric API directly.

    Semantically identical to ``hierarchical_exchange`` run under
    ``shard_map`` with nodes laid out pod-major (node ``k`` lives in pod
    ``k // (n_nodes // n_pods)``): each destination merges its own
    backplane's egress first (node-major, gated by ``intra_enables``), then
    every backplane's egress pod-major (gated by ``inter_enables`` with the
    own pod excluded), packs to ``capacity`` and applies its rev LUT.
    Only validity masks are per-destination; labels stay shared views.

    Sparsity-aware datapath: ``link_capacity`` packs every node's egress to
    that many slots before any merging (only valid, packed events cross an
    MGT lane); ``pod_capacity`` additionally packs each backplane's
    aggregated egress before the pod-major layer-2 merge, shrinking
    inter-backplane traffic from ``per·cap_in`` to ``pod_capacity`` per pod.
    Overflow at either stage is an *uplink* drop, counted separately from
    destination congestion.  With both ``None`` (or ≥ the raw stream sizes)
    the round is bit-exact with the dense datapath.

    Args:
      state: stacked routing state for all ``n_pods * per_pod`` nodes.
      frames: per-node egress frames [n_nodes, cap_in], pod-major.
      capacity: ingress frame capacity per node.
      n_pods: number of backplanes (must divide n_nodes).
      intra_enables: bool[per_pod, per_pod] routes within each backplane.
      inter_enables: bool[n_pods, n_pods] routes between backplanes.
      link_capacity: per-lane egress pack size (``None`` = dense frames).
      pod_capacity: per-pod layer-2 uplink pack size (``None`` = dense).
      timing: timed datapath — ``frames.times`` are departure timestamps and
        the ingress ``times`` are arrival timestamps (fixed path + sender
        lane + pod uplink + destination merge queueing; inter-backplane
        events additionally pay ``second_layer_extra_ns``).  ``None`` keeps
        the untimed wire (ingress times are zeros).

    Returns:
      (ingress frames [n_nodes, capacity],
       ExchangeDrops(congestion [n_nodes], uplink [n_nodes])).
    """
    n_nodes = frames.labels.shape[0]
    if n_nodes % n_pods:
        raise ValueError(f"{n_nodes} nodes do not fill {n_pods} pods evenly")
    plan = fablib.compile_fabric(fablib.hierarchical_spec(
        n_pods=n_pods, per_pod=n_nodes // n_pods, capacity=capacity,
        intra_enables=intra_enables, inter_enables=inter_enables,
        link_capacity=link_capacity, pod_capacity=pod_capacity))
    return fablib.fabric_route_step(state, frames, plan, use_fused=use_fused,
                                    timing=timing)


def route_step_baseline(state: RouterState, frames: EventFrame,
                        capacity: int) -> tuple[EventFrame, jax.Array]:
    """The seed's datapath: broadcast materialization + stable argsort.

    Retired from the hot path; kept so benchmarks can report before/after
    and tests can pin drop-count/order semantics against it.
    """
    wire, fwd_en = jax.vmap(routing.lookup_fwd)(state.fwd_tables, frames.labels)
    egress = EventFrame(labels=wire, times=jnp.zeros_like(frames.times),
                        valid=frames.valid & fwd_en)
    mixed, dropped = routing.aggregate_baseline(egress, state.route_enables,
                                                capacity)
    chip, rev_en = jax.vmap(routing.lookup_rev)(state.rev_tables, mixed.labels)
    valid = mixed.valid & rev_en
    ingress = EventFrame(labels=jnp.where(valid, chip, 0), times=mixed.times,
                         valid=valid)
    return ingress, dropped


# ---------------------------------------------------------------------------
# Sharded datapath: call inside shard_map, one node per mesh slice
# ---------------------------------------------------------------------------


def star_exchange(frame: EventFrame,
                  axis_name: str,
                  fwd_table: jax.Array,
                  rev_table: jax.Array,
                  route_enables: jax.Array,
                  capacity: int,
                  use_fused: bool | None = None,
                  link_capacity: int | None = None,
                  timing: TimedWire | None = None
                  ) -> tuple[EventFrame, ExchangeDrops]:
    """One exchange round from the perspective of a single node shard.

    .. deprecated:: prefer ``repro.core.fabric`` — this is a thin wrapper
       over the 1-level fabric plan (``fabric.star_spec`` +
       ``fabric.fabric_exchange``); N-level meshes go through
       ``fabric.FabricInterconnect`` directly.

    Must run inside ``shard_map``.  ``frame`` holds this node's egress events
    with shape [cap_in]; the return value is this node's ingress frame plus
    its ``ExchangeDrops`` (scalars: congestion at this destination, uplink
    overflow at this sender).

    The ``all_gather`` along ``axis_name`` is the star's up-link + broadcast;
    destination-side filtering with ``route_enables[src, me]``, the merge,
    the capacity pack and the reverse LUT happen locally — mirroring the
    hardware where route enables live in the Aggregator and reverse LUTs in
    each receiving Node-FPGA.  The fwd LUT runs on the *sender* before the
    gather, so only wire labels travel; timestamps are discarded at egress
    (§III) and never gathered at all.

    Sparsity-aware wire path: with ``link_capacity`` set, the sender packs
    its egress to that many slots before the gather (only valid, packed
    events cross the MGT lane; overflow is an uplink drop).  Either way the
    gathered stream travels as int16 wire words (15-bit label + valid flag,
    ``events.pack_wire16``), halving gather bandwidth vs int32 labels plus a
    mask; the words are unpacked inside the merge kernel.

    Timed datapath (``timing`` set): an int32 timestamp lane rides alongside
    the wire words — ``frame.times`` are departures, the ingress ``times``
    arrivals (fixed path + sender-lane wait + destination merge queueing).
    """
    plan = fablib.compile_fabric(fablib.star_spec(
        route_enables.shape[0], capacity, enables=route_enables,
        link_capacity=link_capacity))
    return fablib.fabric_exchange(frame, (axis_name,), fwd_table, rev_table,
                                  plan, use_fused=use_fused, timing=timing)


def hierarchical_exchange(frame: EventFrame,
                          node_axis: str,
                          pod_axis: str,
                          fwd_table: jax.Array,
                          rev_table: jax.Array,
                          intra_enables: jax.Array,
                          inter_enables: jax.Array,
                          capacity: int,
                          use_fused: bool | None = None,
                          link_capacity: int | None = None,
                          pod_capacity: int | None = None,
                          timing: TimedWire | None = None
                          ) -> tuple[EventFrame, ExchangeDrops]:
    """Two-layer star (§V): backplane aggregators joined by a second-layer node.

    .. deprecated:: prefer ``repro.core.fabric`` — this is a thin wrapper
       over the 2-level fabric plan (``fabric.hierarchical_spec`` +
       ``fabric.fabric_exchange``); N-level meshes go through
       ``fabric.FabricInterconnect`` directly.

    ``intra_enables``: bool[n_node, n_node] routes within the backplane.
    ``inter_enables``: bool[n_pod, n_pod] routes between backplanes (whole
    backplanes are the second layer's endpoints; finer control belongs in the
    reverse LUTs, as in hardware).

    Intra-backplane traffic takes one gather (2 MGT hops); inter-backplane
    traffic takes both gathers (4 hops → the projected extra ≈0.4 µs).

    Sparsity-aware wire path: ``link_capacity`` packs this node's egress
    before the layer-1 gather; ``pod_capacity`` packs the backplane's
    aggregated egress before the layer-2 gather, so inter-backplane traffic
    shrinks from ``n_node·cap_in`` to ``pod_capacity`` words per pod.
    Overflow at either pack is an uplink drop (the pod-uplink loss is seen
    by — and attributed to — every node of the pod).  Both gathers move
    int16 wire words (``events.pack_wire16``), unpacked inside the merge.
    With both capacities ``None`` (or ≥ the raw sizes) the round is
    bit-exact with the dense datapath.

    Timed datapath (``timing`` set): the int32 timestamp lane rides both
    gathers; inter-backplane events additionally pay the §V fixed extra and
    the pod uplink lane's serialization wait before the layer-2 gather.
    """
    plan = fablib.compile_fabric(fablib.hierarchical_spec(
        n_pods=inter_enables.shape[0], per_pod=intra_enables.shape[0],
        capacity=capacity, intra_enables=intra_enables,
        inter_enables=inter_enables, link_capacity=link_capacity,
        pod_capacity=pod_capacity))
    return fablib.fabric_exchange(frame, (node_axis, pod_axis), fwd_table,
                                  rev_table, plan, use_fused=use_fused,
                                  timing=timing)


# ---------------------------------------------------------------------------
# Convenience wrapper binding a mesh + specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StarInterconnect:
    """Builds shard_map'd exchange functions over a device mesh.

    .. deprecated:: prefer ``fabric.FabricInterconnect`` — this wrapper
       covers the 1-level star and the 2-level hierarchy with the legacy
       call signature (route enables as runtime arguments); the fabric
       binding takes the enables from the compiled plan and scales to any
       number of nested mesh axes.

    ``exchange_fn`` dispatches one round; ``stream_fn`` is the streaming
    engine's sharded entry point — it scans T rounds inside a *single*
    ``shard_map``, with the routing tables hoisted to loop invariants, so a
    whole emulation run is one compiled program instead of T dispatches.

    ``use_fused=None`` (default) resolves through ``fused_exchange_enabled``
    at trace time, so the fused route-merge-pack kernel runs inside the
    shard_map'd exchange unless explicitly disabled.

    ``link_capacity`` / ``pod_capacity`` switch on the compact-before-gather
    uplink stages (see ``star_exchange`` / ``hierarchical_exchange``); the
    returned drop counts are ``ExchangeDrops`` pytrees either way.
    ``link_capacity`` may also come from the transceiver model: pass a
    ``link.LinkConfig`` whose ``link_capacity`` field is set (explicit
    ``link_capacity`` wins when both are given).
    """

    mesh: jax.sharding.Mesh
    node_axis: str
    pod_axis: str | None = None
    capacity: int = 256
    use_fused: bool | None = None
    link_capacity: int | None = None
    pod_capacity: int | None = None
    link: "LinkConfig | None" = None
    # Timed datapath: thread the int32 timestamp lane through the exchange
    # (``latency.timed_wire``); ``None`` keeps the untimed wire.
    timing: TimedWire | None = None

    def _link_capacity(self) -> int | None:
        if self.link_capacity is not None:
            return self.link_capacity
        return self.link.link_capacity if self.link is not None else None

    def _round(self):
        """Shared per-shard round: ``(round_fn, frame_spec, table_specs)``.

        ``round_fn(frame, *tables)`` runs one exchange for this shard's
        [cap_in] frame (tables carry their leading size-1 sharded dim);
        both ``exchange_fn`` and ``stream_fn`` wrap it, so the two entry
        points cannot drift apart.  The round compiles the 1- or 2-level
        fabric plan from the runtime enables and runs ``fabric_exchange``.
        """
        from jax.sharding import PartitionSpec as P

        node, pod = self.node_axis, self.pod_axis
        cap = self.capacity
        fused = self.use_fused
        timing = self.timing
        link_cap, pod_cap = self._link_capacity(), self.pod_capacity
        if pod is None:
            if pod_cap is not None:
                raise ValueError("pod_capacity requires a pod_axis (the "
                                 "layer-2 uplink only exists on the "
                                 "hierarchical topology)")

            def round_fn(frame, fwd, rev, enables):
                return star_exchange(frame, node, fwd[0], rev[0], enables,
                                     cap, use_fused=fused,
                                     link_capacity=link_cap, timing=timing)
            shard = P(node)
            table_specs = (P(node), P(node), P())
        else:
            def round_fn(frame, fwd, rev, intra, inter):
                return hierarchical_exchange(frame, node, pod, fwd[0],
                                             rev[0], intra, inter, cap,
                                             use_fused=fused,
                                             link_capacity=link_cap,
                                             pod_capacity=pod_cap,
                                             timing=timing)
            shard = P((pod, node))
            table_specs = (shard, shard, P(), P())
        return round_fn, shard, table_specs

    def exchange_fn(self):
        round_fn, shard, table_specs = self._round()
        # Per-node leaves keep a leading size-1 sharded dim inside shard_map;
        # squeeze it on entry and restore it on exit.

        def fn(frame, *tables):
            out, drops = round_fn(jax.tree.map(lambda x: x[0], frame),
                                  *tables)
            return (jax.tree.map(lambda x: x[None], out),
                    jax.tree.map(lambda x: x[None], drops))

        in_specs = (EventFrame(shard, shard, shard), *table_specs)
        out_specs = (EventFrame(shard, shard, shard),
                     ExchangeDrops(shard, shard, shard, shard))
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs))

    def stream_fn(self):
        """Multi-step exchange: scan T rounds inside one ``shard_map``.

        The returned function takes frames whose leaves carry a leading time
        axis ([T, n_nodes, cap_in]) plus the same table arguments as
        ``exchange_fn``, and returns ([T, n_nodes, capacity] ingress frames,
        [T, n_nodes] dropped counts).  Tables enter the scan as closed-over
        invariants — staged into device memory once for the whole stream.
        """
        from jax.sharding import PartitionSpec as P

        round_fn, shard, table_specs = self._round()

        def fn(frames, *tables):
            frames = jax.tree.map(lambda x: x[:, 0], frames)  # [T, cap_in]

            def body(_, fr):
                return None, round_fn(fr, *tables)

            _, (outs, drops) = jax.lax.scan(body, None, frames)
            return (jax.tree.map(lambda x: x[:, None], outs),
                    jax.tree.map(lambda x: x[:, None], drops))

        tshard = P(None, *shard)                  # leading time axis
        in_specs = (EventFrame(tshard, tshard, tshard), *table_specs)
        out_specs = (EventFrame(tshard, tshard, tshard),
                     ExchangeDrops(tshard, tshard, tshard, tshard))
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs))
