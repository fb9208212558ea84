"""Fabric: arbitrary N-level topologies compiled into one hop-graph executor.

The paper's Aggregator exposes 12 backplane links *plus 4 transceiver lanes
"for further extension"*, and §V projects growth beyond the two-level
120-chip system.  This module generalizes the star / two-layer special
cases into a declarative topology description that **compiles** to a
hop-graph plan executed by one generic engine:

* ``LevelSpec`` / ``FabricSpec`` — levels of fan-in, per-level uplink (link)
  capacities (explicit, from a ``link.LinkConfig``, or derived from the lane
  model via ``events_per_window``), per-level route enables, per-level
  ``LatencyParams`` for the crossing extras, and the extension-lane
  constraint (a level riding the Aggregator's extension lanes cannot join
  more than ``interconnect.EXTENSION_LANES`` children).
* ``compile_fabric`` → ``FabricPlan`` — the static hop graph: per-level
  fan-ins, enables, compact-before-gather capacities, crossing extras
  (integer ns, ``TimedWire``-compatible), and the per-destination merge
  segment layout the pack units tile over.
* ``fabric_route_step`` — the stacked single-device executor: one exchange
  round for all leaves, N levels deep, reusing the existing Pallas
  ``exchange_fwd`` (1-level fast path) and ``merge_pack_fwd`` kernels.
* ``fabric_exchange`` — the per-shard executor for ``shard_map``: one mesh
  axis per level (nested meshes), per-level ``all_gather`` + uplink packs,
  16-bit wire words on every gather, same merge tail.  Under
  ``exchange_mode="routed"`` the gathers become per-level ``ppermute``
  neighbor exchanges that move only the hop-graph edges (the paper's
  point-to-point transceiver links, never a broadcast), bit-exact with the
  gather strategy.
* ``FabricInterconnect`` — the mesh binding (N nested axes), with
  ``exchange_fn`` / ``stream_fn`` like the legacy ``StarInterconnect``.

The four legacy entry points (``route_step``, ``route_step_hierarchical``,
``star_exchange``, ``hierarchical_exchange``) and ``StarInterconnect`` in
``repro.core.aggregator`` are thin wrappers over 1-level and 2-level plans —
bit-exact with their pre-fabric implementations, timed lane included.

Hop-graph semantics (generalizing §III/§V):

Leaves are the ``prod(fan_in)`` Node-FPGA endpoints.  A tier-``i`` entity
(tier 0 = leaf, tier 1 = backplane, tier 2 = 4U case, ...) uplinks its
aggregated egress stream ``U_i`` into the tier-``i+1`` merge; crossing level
``i+1`` optionally packs the stream to that level's ``link_capacity``
(compact-before-gather; overflow is an uplink drop attributed to every leaf
of the entity) — packs *cascade*, so an event crossing k levels must survive
every intermediate uplink, exactly like the hardware path through each
aggregator.  A destination leaf merges, nearest first:

    level 1:  the ``U_0`` lanes of its own backplane (leaf-major),
    level 2:  the ``U_1`` streams of the sibling backplanes in its case,
    level 3:  the ``U_2`` streams of the sibling cases, ...

gated by that level's route enables (own subtree excluded above level 1),
then packs to the ingress ``capacity`` and applies the reverse LUT.  On the
timed datapath every level-``i+1`` crossing adds its fixed extra (default:
the §V ``second_layer_extra_ns`` per crossing) plus the uplink lane's
serialization wait of the event's rank in the entity stream.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import routing
from repro.core.events import (EventFrame, make_frame, make_frame_segmented,
                               pack_wire16, unpack_wire16)
from repro.core.interconnect import (BACKPLANES_PER_RACK, CHIPS_PER_BACKPLANE,
                                     EXTENSION_LANES)
from repro.core.latency import (LatencyParams, TimedWire,
                                queue_wait_i32 as _queue_wait_i32)
from repro.core.link import LinkConfig


def fused_exchange_enabled() -> bool:
    """Default for ``use_fused`` — env-gated, on unless REPRO_FUSED_EXCHANGE=0."""
    import os

    return os.environ.get("REPRO_FUSED_EXCHANGE", "1").lower() not in (
        "0", "false", "off")


class ExchangeDrops(NamedTuple):
    """Loss accounting of one exchange round, split by drop point.

    ``congestion``: destination pack-unit overflow (the receiving mux drops
    under continued congestion — the paper's layer-1 loss semantics).
    ``uplink``: sender-side overflow of the compact-before-gather stages —
    events exceeding a level's ``link_capacity`` on any uplink of the hop
    graph (higher-level overflow is attributed to every leaf of the packed
    entity, whose gathered view loses the same events).
    ``unroutable``: events killed by a dead edge with no surviving route —
    a dead uplink without an extension-lane detour masks the whole entity
    stream (attributed, like uplink drops, to every leaf of the subtree); a
    dead downlink masks the destinations below it (attributed per
    destination leaf, once per destination that lost the event).
    ``rerouted`` is *not* a loss: events that crossed a dead uplink via a
    sibling's spare extension lanes (they arrive, paying the detour's extra
    crossing on the timed lane), attributed like uplink drops.
    All four are 0-filled int32 arrays of matching shape; ``total`` sums
    the three loss classes (``rerouted`` excluded — those events arrive).
    """

    congestion: jax.Array
    uplink: jax.Array
    unroutable: jax.Array
    rerouted: jax.Array

    @property
    def total(self) -> jax.Array:
        return self.congestion + self.uplink + self.unroutable


# ---------------------------------------------------------------------------
# Timed datapath helpers (integer-ns timestamp lane, see latency.timed_wire)
# ---------------------------------------------------------------------------


def _egress_times(frame_times: jax.Array, ev: jax.Array,
                  timing: TimedWire) -> jax.Array:
    """Sender-side arrival times at the first merge input: departure + fixed
    sender path + the MGT uplink lane's serialization wait of each event's
    egress rank.  Computed on the *unpacked* egress so the compact-before-
    gather pack (which preserves order) cannot change timestamps —
    capacity parity holds for the timestamp lane too."""
    ok = ev.astype(jnp.int32)
    rank = jnp.cumsum(ok, axis=-1) - ok
    wait = _queue_wait_i32(rank, timing.uplink_queue)
    return jnp.where(ev, frame_times.astype(jnp.int32)
                     + timing.sender_fixed_ns + wait, 0)


def _arrival_times(out_times: jax.Array, out_valid: jax.Array,
                   timing: TimedWire) -> jax.Array:
    """Receiver-side fixed path, applied after the merge (which already
    added the destination's rank-dependent queueing in the pack)."""
    return jnp.where(out_valid, out_times + timing.recv_fixed_ns, 0)


def kernel_mode(use_fused: bool) -> str:
    """Kernel mode for the fused merges and the stream's egress pack,
    resolved *eagerly* (never ``None``) so the ops-level jit caches one
    entry per concrete mode — the chip compile tests and parity tests
    monkeypatch ``repro.kernels.default_mode`` and must not hit a stale
    ``mode=None`` trace."""
    from repro.kernels import default_mode

    return default_mode() if use_fused else "jax"


def _fused_merge(labels, valid, rev, capacity: int, *, seg_lens, compact,
                 timing: TimedWire | None, use_fused: bool | None,
                 times=None) -> tuple[EventFrame, jax.Array]:
    """The shared merge tail of every exchange path: ``fused_merge_pack``
    (timed lane + destination queue when ``timing`` is set) and assembly of
    the ingress frame with arrival times (zeros on the untimed wire)."""
    from repro.kernels.spike_router.ops import fused_merge_pack

    outs = fused_merge_pack(
        labels, valid, rev, capacity=capacity, seg_lens=seg_lens,
        compact=compact, times=times,
        queue=None if timing is None else timing.queue,
        mode=kernel_mode(use_fused))
    if timing is not None:
        out_l, out_v, out_t, dropped = outs
        out_t = _arrival_times(out_t, out_v, timing)
    else:
        out_l, out_v, dropped = outs
        out_t = jnp.zeros_like(out_l)
    return EventFrame(labels=out_l, times=out_t, valid=out_v), dropped


# ---------------------------------------------------------------------------
# Topology description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LevelSpec:
    """One level of the hop graph: a node joining ``fan_in`` children.

    Attributes:
      fan_in: children (leaves at level 1, lower-level subtrees above) one
        node at this level joins.
      enables: bool[fan_in, fan_in] static route-enable matrix between the
        node's children (shared by every node of this level, like the
        paper's per-backplane ``intra_enables``).  ``None`` = all-to-all
        (without self-loops at level 1; own-subtree traffic above level 1 is
        structurally excluded — it already travelled a lower level).
      link_capacity: events each child's uplink admits per exchange round —
        the compact-before-gather pack size into this level's merge
        (``None`` = dense, the whole stream travels).  At level 1 this is
        the Node-FPGA→Aggregator MGT lane; above it, the subtree's uplink
        into the joining node (the two-level ``pod_capacity``).
      link: derive ``link_capacity`` from the transceiver model instead —
        the config's own ``link_capacity`` field if set, else
        ``link.events_per_window(spec.window_us)`` (the hardware-faithful
        sizing).  An explicit ``link_capacity`` wins over both.
      latency: per-level ``LatencyParams`` for the *crossing extras* of the
        timed datapath: events crossing this level (2+) pay
        ``latency.second_layer_extra_ns()``.  ``None`` defers to the
        executor's ``TimedWire.second_layer_extra_ns`` per crossing.
      extension: this level's children ride the Aggregator's extension
        lanes — ``fan_in`` may not exceed ``interconnect.EXTENSION_LANES``.
      uplink_health: static per-edge health of this level's uplinks — one
        bool per child entity crossing into this level's merge, *globally*
        (length ``n_nodes // prod(fan_in below)``; entity-major, so edge
        ``e`` is slot ``e % fan_in`` of group ``e // fan_in``).  ``None`` /
        all-True = healthy.  A dead uplink above level 1 is detoured
        through a healthy sibling's spare extension lanes when one has
        budget (see ``compile_fabric``); dead leaf lanes (level 1) and
        detour-exhausted edges make the subtree's events ``unroutable``.
      downlink_health: static per-edge health of the node→child broadcast
        downlinks, same indexing.  No detour exists downstream (the merge
        result descends one fixed path), so destinations below a dead
        downlink count every event addressed to them as ``unroutable``.
    """

    fan_in: int
    enables: jax.Array | None = None
    link_capacity: int | None = None
    link: LinkConfig | None = None
    latency: LatencyParams | None = None
    extension: bool = False
    uplink_health: tuple[bool, ...] | None = None
    downlink_health: tuple[bool, ...] | None = None


@dataclasses.dataclass(frozen=True)
class FabricSpec:
    """A declarative N-level topology, leaf level first.

    ``window_us`` is the exchange-window duration used to derive
    ``link_capacity`` for levels that specify a ``LinkConfig`` without an
    event budget (``LinkConfig.events_per_window``).  ``reroute`` lets
    ``compile_fabric`` assign extension-lane detours around dead uplinks
    (the paper's 4 spare transceiver lanes); ``False`` compiles pure
    masking — dead edges drop their traffic as ``unroutable`` instead.
    ``exchange_mode`` selects the wire strategy: ``"gather"`` broadcasts
    each level's streams (one ``all_gather`` per level in the sharded
    executor, full-plane merges in the stacked one); ``"routed"`` moves
    only the hop-graph edges — ``ppermute`` neighbor exchanges per level
    on devices, per-destination enabled-source merge schedules stacked —
    with identical observables (see ``with_exchange_mode``,
    ``pick_exchange_mode``).
    """

    levels: tuple[LevelSpec, ...]
    capacity: int
    window_us: float | None = None
    name: str = ""
    reroute: bool = True
    exchange_mode: str = "gather"

    @property
    def n_nodes(self) -> int:
        return math.prod(lvl.fan_in for lvl in self.levels)


@dataclasses.dataclass(frozen=True)
class LevelPlan:
    """Compiled static state of one hop-graph level."""

    fan_in: int
    enables: jax.Array         # bool[fan_in, fan_in]
    link_capacity: int | None  # per-child uplink pack into this level
    extra_ns: int | None       # timed crossing extra; None = TimedWire default
    leaves: int                # leaves under one node of this level
    uplink_ok: np.ndarray | None = None    # bool[n_edges]; None = all healthy
    detour: np.ndarray | None = None       # int32[n_edges] host edge, -1 none
    downlink_ok: np.ndarray | None = None  # bool[n_edges]; None = all healthy

    @property
    def routable(self) -> np.ndarray | None:
        """Edges whose traffic survives: alive, or detoured via a host."""
        if self.uplink_ok is None:
            return None
        return self.uplink_ok | (self.detour >= 0)

    @property
    def degraded(self) -> bool:
        return self.uplink_ok is not None or self.downlink_ok is not None

    def detour_counts(self) -> np.ndarray | None:
        """Detours hosted per uplink edge (index = the *host* edge) — the
        static-analysis view of the extension-lane budget: every entry must
        stay ≤ ``interconnect.EXTENSION_LANES``.  ``None`` when healthy."""
        if self.detour is None:
            return None
        hosts = self.detour[self.detour >= 0]
        return np.bincount(hosts, minlength=self.detour.shape[0])


@dataclasses.dataclass(frozen=True)
class FabricPlan:
    """The compiled hop graph: what the executors consume.

    ``merge_layout(cap_in)`` returns, per level, the static segment lengths
    of that level's contribution to a destination's merge stream (the pack
    units tile over these); ``compact`` says every segment is
    front-compacted (leaf lanes packed), enabling the bounded per-segment
    gather.
    """

    spec: FabricSpec
    levels: tuple[LevelPlan, ...]
    n_nodes: int
    capacity: int

    @property
    def n_levels(self) -> int:
        return len(self.levels)

    @property
    def fan_ins(self) -> tuple[int, ...]:
        return tuple(lvl.fan_in for lvl in self.levels)

    @property
    def compact(self) -> bool:
        return self.levels[0].link_capacity is not None

    @property
    def exchange_mode(self) -> str:
        """Wire strategy ("gather" | "routed") — see ``FabricSpec``."""
        return self.spec.exchange_mode

    @property
    def degraded(self) -> bool:
        """Any level carries static per-edge health (dead uplink/downlink)."""
        return any(lvl.degraded for lvl in self.levels)

    @property
    def edge_counts(self) -> tuple[int, ...]:
        """Per-level uplink/downlink edge counts (children crossing level i)."""
        out, gsize = [], 1
        for lvl in self.levels:
            out.append(self.n_nodes // gsize)
            gsize *= lvl.fan_in
        return tuple(out)

    # -- introspection hooks (the static-analysis surface, repro.analysis) --
    #
    # These expose the hop graph's *addressing* — which entity a leaf is at
    # each tier, through which level a (src, dst) pair's traffic travels,
    # and what the route-enable gate says there — as plain numpy, so the
    # fabric verifier (analysis/planlint.py) can type every pair's delivery
    # without re-deriving the executors' index arithmetic.

    @property
    def group_sizes(self) -> tuple[int, ...]:
        """Leaves per tier-``i`` entity feeding level ``i``'s merge (tier 0 =
        leaf): ``(1, f0, f0·f1, ...)``, one entry per level."""
        out, g = [], 1
        for lvl in self.levels:
            out.append(g)
            g *= lvl.fan_in
        return tuple(out)

    def leaf_entities(self, level: int) -> np.ndarray:
        """int[n_nodes]: each leaf's tier-``level`` entity index — the global
        uplink/downlink edge its traffic crosses into that level's merge."""
        return np.arange(self.n_nodes) // self.group_sizes[level]

    def delivery_levels(self) -> np.ndarray:
        """int32[n, n]: the unique hop-graph level through which ``src``'s
        stream joins ``dst``'s merge — the lowest level whose joining node
        covers both leaves (health and gating not applied)."""
        n = self.n_nodes
        out = np.full((n, n), -1, np.int32)
        leaf = np.arange(n)
        for i in reversed(range(self.n_levels)):
            anc = leaf // (self.group_sizes[i] * self.levels[i].fan_in)
            same = anc[:, None] == anc[None, :]
            out = np.where(same, np.int32(i), out)
        return out

    def level_gate(self, level: int) -> np.ndarray:
        """bool[n, n]: the route-enable gate the executors apply to (src,
        dst) pairs whose traffic merges at ``level`` —
        ``enables[src_child, dst_child]`` plus the structural own-subtree
        exclusion above level 0.  Only meaningful where
        ``delivery_levels() == level``."""
        lvl = self.levels[level]
        child = self.leaf_entities(level) % lvl.fan_in
        en = np.asarray(lvl.enables)
        gate = en[np.ix_(child, child)]
        if level > 0:
            gate = gate & (child[:, None] != child[None, :])
        return gate

    def merge_layout(self, cap_in: int) -> tuple[tuple[int, ...], ...]:
        """Per-level merge segment lengths for egress frames of ``cap_in``."""
        u0 = self.levels[0].link_capacity
        segs_u = (u0,) if u0 is not None else (cap_in,)
        out = []
        for i, lvl in enumerate(self.levels):
            out.append(segs_u * lvl.fan_in)
            if i + 1 < len(self.levels):
                nxt = self.levels[i + 1]
                segs_u = ((nxt.link_capacity,) if nxt.link_capacity is not None
                          else segs_u * lvl.fan_in)
        return tuple(out)

    def identity_tables(self, n_labels: int | None = None
                        ) -> tuple[jax.Array, jax.Array]:
        """Stacked identity fwd/rev LUTs for every leaf (testing/benchmarks)."""
        tables = routing.identity_tables(n_labels)
        n = self.n_nodes
        return (jnp.broadcast_to(tables.fwd, (n, tables.fwd.shape[0])),
                jnp.broadcast_to(tables.rev, (n, tables.rev.shape[0])))

    def describe(self) -> str:
        """One-line human summary ('12 x 2 x 4 = 96 leaves, caps 8/30/58')."""
        shape = " x ".join(str(f) for f in self.fan_ins)
        caps = "/".join("-" if lvl.link_capacity is None
                        else str(lvl.link_capacity) for lvl in self.levels)
        name = f"{self.spec.name}: " if self.spec.name else ""
        return (f"{name}{shape} = {self.n_nodes} leaves, "
                f"capacity {self.capacity}, uplink caps {caps}")


def _parse_health(raw, n_edges: int, what: str) -> np.ndarray | None:
    """Normalize a per-edge health vector: ``None``/all-True → ``None``."""
    if raw is None:
        return None
    health = np.asarray(raw, dtype=bool).reshape(-1)
    if health.shape[0] != n_edges:
        raise ValueError(f"{what} has {health.shape[0]} entries but the "
                         f"level crosses {n_edges} edges")
    return None if bool(health.all()) else health


def _assign_detours(alive: np.ndarray, fan_in: int) -> np.ndarray:
    """Host assignment for dead uplinks: each dead child entity detours its
    stream through the nearest healthy sibling's spare Aggregator lanes
    (ring distance within the group, ties to the lower slot), each host
    taking at most ``EXTENSION_LANES`` detours — the paper's 4 spare
    transceiver lanes.  Returns the global host edge index per edge, -1 for
    healthy edges and for dead edges with no host (detour-exhausted)."""
    n_edges = alive.shape[0]
    detour = np.full(n_edges, -1, np.int32)
    budget = np.zeros(n_edges, np.int32)
    for base in range(0, n_edges, fan_in):
        for j in range(fan_in):
            if alive[base + j]:
                continue
            cands = sorted(
                (min((k - j) % fan_in, (j - k) % fan_in), k)
                for k in range(fan_in) if k != j and alive[base + k])
            for _, k in cands:
                if budget[base + k] < EXTENSION_LANES:
                    detour[base + j] = base + k
                    budget[base + k] += 1
                    break
    return detour


EXCHANGE_MODES = ("gather", "routed")


def compile_fabric(spec: FabricSpec) -> FabricPlan:
    """Compile a topology description into the static hop-graph plan."""
    if not spec.levels:
        raise ValueError("a fabric needs at least one level")
    if spec.capacity <= 0:
        raise ValueError(f"ingress capacity must be positive: {spec.capacity}")
    if spec.exchange_mode not in EXCHANGE_MODES:
        raise ValueError(f"unknown exchange_mode: {spec.exchange_mode!r} "
                         f"(expected one of {EXCHANGE_MODES})")
    n_nodes = spec.n_nodes
    levels = []
    leaves = 1
    for i, lvl in enumerate(spec.levels):
        if lvl.fan_in < 1:
            raise ValueError(f"level {i} fan_in must be >= 1: {lvl.fan_in}")
        if lvl.extension and lvl.fan_in > EXTENSION_LANES:
            raise ValueError(
                f"level {i} rides the {EXTENSION_LANES} Aggregator extension "
                f"lanes but joins {lvl.fan_in} children")
        if lvl.enables is None:
            enables = (routing.full_route_enables(lvl.fan_in) if i == 0
                       else jnp.ones((lvl.fan_in, lvl.fan_in), jnp.bool_))
        else:
            enables = jnp.asarray(lvl.enables).astype(jnp.bool_)
            if enables.shape != (lvl.fan_in, lvl.fan_in):
                raise ValueError(
                    f"level {i} enables shape {enables.shape} does not match "
                    f"fan_in {lvl.fan_in}")
        cap = lvl.link_capacity
        if cap is None and lvl.link is not None:
            if lvl.link.link_capacity is not None:
                cap = lvl.link.link_capacity
            elif spec.window_us is not None:
                cap = lvl.link.events_per_window(spec.window_us)
            else:
                raise ValueError(
                    f"level {i} has a LinkConfig without an event budget; "
                    "set LinkConfig.link_capacity or FabricSpec.window_us "
                    "to derive it from events_per_window")
        if cap is not None and cap < 1:
            raise ValueError(f"level {i} link_capacity must be >= 1: {cap}")
        extra = (None if lvl.latency is None
                 else int(round(lvl.latency.second_layer_extra_ns())))
        n_edges = n_nodes // leaves
        up_ok = _parse_health(lvl.uplink_health, n_edges,
                              f"level {i} uplink_health")
        down_ok = _parse_health(lvl.downlink_health, n_edges,
                                f"level {i} downlink_health")
        detour = None
        if up_ok is not None:
            # Leaf MGT lanes (level 1) have no sibling interconnect to
            # detour over — only Aggregator-tier uplinks can borrow a
            # sibling's spare lanes.
            detour = (_assign_detours(up_ok, lvl.fan_in)
                      if spec.reroute and i > 0
                      else np.full(n_edges, -1, np.int32))
        leaves *= lvl.fan_in
        levels.append(LevelPlan(fan_in=lvl.fan_in, enables=enables,
                                link_capacity=cap, extra_ns=extra,
                                leaves=leaves, uplink_ok=up_ok,
                                detour=detour, downlink_ok=down_ok))
    return FabricPlan(spec=spec, levels=tuple(levels), n_nodes=leaves,
                      capacity=spec.capacity)


def with_exchange_mode(plan: FabricPlan, mode: str) -> FabricPlan:
    """Copy a compiled plan under a different wire strategy.  The levels are
    strategy-independent, so no recompile happens — the two modes share one
    hop graph and differ only in how the executors move the wire words."""
    if mode not in EXCHANGE_MODES:
        raise ValueError(f"unknown exchange_mode: {mode!r} "
                         f"(expected one of {EXCHANGE_MODES})")
    if plan.spec.exchange_mode == mode:
        return plan
    return dataclasses.replace(
        plan, spec=dataclasses.replace(plan.spec, exchange_mode=mode))


# -- convenience spec constructors (the legacy shapes + the §V extension) ----


def star_spec(n_nodes: int, capacity: int, *, enables=None,
              link_capacity: int | None = None,
              link: LinkConfig | None = None,
              window_us: float | None = None, name: str = "") -> FabricSpec:
    """One backplane star: the 1-level fabric behind ``route_step`` /
    ``star_exchange``."""
    return FabricSpec(
        levels=(LevelSpec(fan_in=n_nodes, enables=enables,
                          link_capacity=link_capacity, link=link),),
        capacity=capacity, window_us=window_us, name=name)


def hierarchical_spec(n_pods: int, per_pod: int, capacity: int, *,
                      intra_enables=None, inter_enables=None,
                      link_capacity: int | None = None,
                      pod_capacity: int | None = None,
                      name: str = "") -> FabricSpec:
    """The §V two-layer system: the 2-level fabric behind
    ``route_step_hierarchical`` / ``hierarchical_exchange``."""
    return FabricSpec(
        levels=(LevelSpec(fan_in=per_pod, enables=intra_enables,
                          link_capacity=link_capacity),
                LevelSpec(fan_in=n_pods, enables=inter_enables,
                          link_capacity=pod_capacity)),
        capacity=capacity, name=name)


def ext_4case_spec(capacity: int = 96, *,
                   chips_per_backplane: int = CHIPS_PER_BACKPLANE,
                   backplanes_per_case: int = BACKPLANES_PER_RACK,
                   n_cases: int = 4,
                   link_capacities: tuple[int | None, int | None, int | None]
                   = (None, None, None)) -> FabricSpec:
    """The 3-level extension scenario: two backplanes per 4U case, cases
    chained over the Aggregator's 4 extension lanes (12 x 2 x 4 = 96 chips
    by default)."""
    u0, u1, u2 = link_capacities
    n = chips_per_backplane * backplanes_per_case * n_cases
    return FabricSpec(
        levels=(LevelSpec(fan_in=chips_per_backplane, link_capacity=u0),
                LevelSpec(fan_in=backplanes_per_case, link_capacity=u1),
                LevelSpec(fan_in=n_cases, link_capacity=u2, extension=True)),
        capacity=capacity, name=f"EXT_4CASE_{n}CHIP")


# ---------------------------------------------------------------------------
# Degraded mode: dynamic health overlays and fault schedules
# ---------------------------------------------------------------------------


class FabricHealth(NamedTuple):
    """Dynamic per-edge health overlay for the executors — one bool vector
    per level for uplinks and downlinks (``plan.edge_counts`` lengths; a
    ``None`` entry means that level is fully healthy).  Unlike the static
    health compiled into the plan, the overlay is *traced*: it masks flows
    in-graph (within-plan degradation, no recompile) but cannot reroute —
    an edge masked here loses its traffic as ``unroutable`` even if the
    static plan had assigned it a detour.  Arrays may carry a leading time
    axis when scanned (``health_schedule``)."""

    uplink: tuple
    downlink: tuple


def full_health(plan: FabricPlan) -> FabricHealth:
    """All-healthy dynamic overlay matching ``plan`` (identity element)."""
    counts = plan.edge_counts
    return FabricHealth(
        uplink=tuple(jnp.ones((c,), jnp.bool_) for c in counts),
        downlink=tuple(jnp.ones((c,), jnp.bool_) for c in counts))


def _check_health(plan: FabricPlan, health: FabricHealth) -> None:
    counts = plan.edge_counts
    for side in ("uplink", "downlink"):
        vecs = getattr(health, side)
        if len(vecs) != plan.n_levels:
            raise ValueError(f"health.{side} has {len(vecs)} levels but the "
                             f"plan wires {plan.n_levels}")
        for i, vec in enumerate(vecs):
            if vec is not None and vec.shape[-1] != counts[i]:
                raise ValueError(
                    f"health.{side}[{i}] covers {vec.shape[-1]} edges but "
                    f"level {i} crosses {counts[i]}")


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled link fault for the stream fault injector: the edge
    ``(level, edge)`` dies at ``kill_step`` (inclusive) and — unless
    ``restore_step`` is ``None`` (permanent) — comes back at
    ``restore_step`` (exclusive).  ``kind`` picks the direction."""

    level: int
    edge: int
    kill_step: int
    restore_step: int | None = None
    kind: str = "uplink"


def _check_faults(plan: FabricPlan, faults: Sequence[FaultEvent]) -> None:
    counts = plan.edge_counts
    for ev in faults:
        if ev.kind not in ("uplink", "downlink"):
            raise ValueError(f"unknown fault kind: {ev.kind!r}")
        if not 0 <= ev.level < plan.n_levels:
            raise ValueError(f"fault level {ev.level} outside the "
                             f"{plan.n_levels}-level plan")
        if not 0 <= ev.edge < counts[ev.level]:
            raise ValueError(f"fault edge {ev.edge} outside level "
                             f"{ev.level}'s {counts[ev.level]} edges")
        if ev.restore_step is not None and ev.restore_step <= ev.kill_step:
            raise ValueError(f"fault restore_step {ev.restore_step} must be "
                             f"> kill_step {ev.kill_step}")


def health_schedule(plan: FabricPlan, faults: Sequence[FaultEvent],
                    n_steps: int) -> FabricHealth:
    """Expand a fault schedule into per-step dynamic health masks,
    ``bool[n_steps, n_edges]`` per level (``None`` for untouched levels) —
    the scan inputs of ``run_stream``'s in-graph masking mode."""
    _check_faults(plan, faults)
    counts = plan.edge_counts
    masks = {side: [None] * plan.n_levels for side in ("uplink", "downlink")}
    for ev in faults:
        tbl = masks[ev.kind]
        if tbl[ev.level] is None:
            tbl[ev.level] = np.ones((n_steps, counts[ev.level]), bool)
        stop = n_steps if ev.restore_step is None else min(ev.restore_step,
                                                           n_steps)
        tbl[ev.level][ev.kill_step:stop, ev.edge] = False
    as_jnp = lambda tbl: tuple(None if m is None else jnp.asarray(m)
                               for m in tbl)
    return FabricHealth(uplink=as_jnp(masks["uplink"]),
                        downlink=as_jnp(masks["downlink"]))


def dead_edges_at(faults: Sequence[FaultEvent], step: int
                  ) -> tuple[tuple[int, int, str], ...]:
    """The set of ``(level, edge, kind)`` dead at ``step`` (sorted)."""
    dead = {(ev.level, ev.edge, ev.kind) for ev in faults
            if ev.kill_step <= step
            and (ev.restore_step is None or step < ev.restore_step)}
    return tuple(sorted(dead))


def fault_boundaries(faults: Sequence[FaultEvent], n_steps: int
                     ) -> tuple[int, ...]:
    """Segment starts where the dead-edge set changes (always includes 0) —
    the recompile points of ``run_stream``'s reroute mode."""
    marks = {0}
    for ev in faults:
        marks.add(ev.kill_step)
        if ev.restore_step is not None:
            marks.add(ev.restore_step)
    return tuple(sorted(m for m in marks if 0 <= m < n_steps))


def shift_faults(faults: Sequence[FaultEvent], start: int, n_steps: int
                 ) -> tuple[FaultEvent, ...]:
    """Rebase a global-step fault schedule onto the window
    ``[start, start + n_steps)`` — the per-window view that windowed
    supervision (``runtime.elastic.run_supervised_stream``) feeds each
    ``run_stream`` call, so a schedule expressed in whole-run steps degrades
    every window exactly as one long run would.  Events entirely outside the
    window are dropped; a kill before the window clamps to local step 0; a
    restore at or past the window end becomes permanent within the window.
    """
    end = start + n_steps
    out = []
    for ev in faults:
        if ev.kill_step >= end:
            continue
        if ev.restore_step is not None and ev.restore_step <= start:
            continue
        restore = (None if ev.restore_step is None or ev.restore_step >= end
                   else ev.restore_step - start)
        out.append(dataclasses.replace(
            ev, kill_step=max(ev.kill_step - start, 0), restore_step=restore))
    return tuple(out)


def degrade_spec(spec: FabricSpec,
                 dead: Iterable[tuple[int, int] | tuple[int, int, str]],
                 *, reroute: bool | None = None) -> FabricSpec:
    """Copy ``spec`` with the given edges marked dead — ``dead`` holds
    ``(level, edge)`` or ``(level, edge, kind)`` tuples (kind defaults to
    ``'uplink'``).  Existing health on the spec is preserved and further
    degraded; ``reroute`` overrides the spec's detour policy.  Compile the
    result to get the degraded plan (detours assigned there)."""
    n_nodes = spec.n_nodes
    health = {}
    gsize = 1
    for i, lvl in enumerate(spec.levels):
        n_edges = n_nodes // gsize
        health[(i, "uplink")] = np.ones(n_edges, bool) if (
            lvl.uplink_health is None) else np.asarray(lvl.uplink_health,
                                                       bool).copy()
        health[(i, "downlink")] = np.ones(n_edges, bool) if (
            lvl.downlink_health is None) else np.asarray(lvl.downlink_health,
                                                         bool).copy()
        gsize *= lvl.fan_in
    for entry in dead:
        level, edge, kind = entry if len(entry) == 3 else (*entry, "uplink")
        if (level, kind) not in health:
            raise ValueError(f"unknown fault kind or level: {kind!r}/{level}")
        if not 0 <= edge < health[(level, kind)].shape[0]:
            raise ValueError(f"edge {edge} outside level {level}'s "
                             f"{health[(level, kind)].shape[0]} edges")
        health[(level, kind)][edge] = False
    new_levels = tuple(
        dataclasses.replace(
            lvl,
            uplink_health=tuple(bool(b) for b in health[(i, "uplink")]),
            downlink_health=tuple(bool(b) for b in health[(i, "downlink")]))
        for i, lvl in enumerate(spec.levels))
    return dataclasses.replace(
        spec, levels=new_levels,
        reroute=spec.reroute if reroute is None else reroute)


def _flow_masks(lvl: LevelPlan, dyn_up, n_ent: int):
    """Combined static+dynamic uplink masks for one level: ``flow_ok`` (the
    edge's traffic survives — alive or detoured, and not dynamically
    masked) and ``live_detour`` (actually travelling a detour), both
    bool[n_ent]; ``(None, None)`` when the level is fully healthy."""
    if lvl.uplink_ok is None and dyn_up is None:
        return None, None
    if lvl.uplink_ok is not None:
        routable = jnp.asarray(lvl.routable)
        detoured = jnp.asarray(~lvl.uplink_ok & (lvl.detour >= 0))
    else:
        routable = jnp.ones((n_ent,), jnp.bool_)
        detoured = jnp.zeros((n_ent,), jnp.bool_)
    if dyn_up is not None:
        return routable & dyn_up, detoured & dyn_up
    return routable, detoured


def _down_mask(lvl: LevelPlan, dyn_down, ent):
    """Per-leaf downlink health of one level (``ent`` = each leaf's child
    entity index at this level), or ``None`` when fully healthy."""
    if lvl.downlink_ok is None and dyn_down is None:
        return None
    ok = None
    if lvl.downlink_ok is not None:
        ok = jnp.asarray(lvl.downlink_ok)[ent]
    if dyn_down is not None:
        dyn = dyn_down[ent]
        ok = dyn if ok is None else ok & dyn
    return ok


def _detour_penalty(lvl: LevelPlan, timing: TimedWire, valid) -> jax.Array:
    """Timed cost of the extension-lane detour: one extra crossing of this
    level (its ``extra_ns``) plus the host lane's serialization wait of the
    event's rank within the detoured stream."""
    ok = valid.astype(jnp.int32)
    rank = jnp.cumsum(ok, axis=-1) - ok
    extra = (lvl.extra_ns if lvl.extra_ns is not None
             else timing.second_layer_extra_ns)
    return extra + _queue_wait_i32(rank, timing.uplink_queue)


# ---------------------------------------------------------------------------
# Routed mode: static edge schedules (hop-graph edges only, no broadcast)
# ---------------------------------------------------------------------------


def _concrete_enables(enables) -> np.ndarray:
    """Routed mode compiles a static edge schedule from the route enables."""
    if isinstance(enables, jax.core.Tracer):
        raise ValueError(
            "exchange_mode='routed' compiles a static edge schedule from the "
            "plan's route enables, which are traced here — build the plan "
            "outside jit (concrete enables) or use exchange_mode='gather'")
    return np.asarray(enables, dtype=bool)


# Keyed by (n, gsize, fan_in, level>0, enables bytes); the values are device
# arrays, so every retrace of the same plan closes over the same staged LUT
# buffers (persistent device constants — they stay small scan constants
# under jaxprlint's program.scan-const rule instead of fresh per-trace
# copies).
_ROUTED_MAP_CACHE: dict = {}


def _routed_leaf_maps(enables, level: int, n: int, gsize: int, f: int):
    """Static per-destination source schedule of one stacked level.

    Returns ``(src_flat, live, deg)``: ``src_flat`` is int32[f·deg] — for
    each destination child slot, the ``deg`` child slots of its enabled
    sources in ascending order (own-subtree excluded above level 0),
    padded with slot 0 where ``live`` (bool[n, deg], already expanded per
    destination leaf) is False; ``deg`` is the max in-degree.  These are
    the hop-graph edges: a route-disabled (or structurally excluded) pair
    never enters the merge stream at all, instead of riding along
    gated-off.
    """
    en = _concrete_enables(enables)
    key = (n, gsize, f, min(level, 1), en.tobytes())
    hit = _ROUTED_MAP_CACHE.get(key)
    if hit is None:
        need = en & ~np.eye(f, dtype=bool) if level > 0 else en
        deg = max(1, int(need.sum(axis=0).max()))
        src = np.zeros((f, deg), np.int32)
        live = np.zeros((f, deg), bool)
        for k in range(f):
            js = np.flatnonzero(need[:, k])
            src[k, :len(js)] = js
            live[k, :len(js)] = True
        child = (np.arange(n) // gsize) % f
        # Concrete device arrays even when called under a trace, so the
        # cache holds persistent buffers, not leaked tracers.
        with jax.ensure_compile_time_eval():
            hit = (jnp.asarray(src.reshape(-1)), jnp.asarray(live[child]),
                   deg)
        _ROUTED_MAP_CACHE[key] = hit
    return hit


def _repeat_rows(x: jax.Array, reps: int) -> jax.Array:
    """Repeat each row ``reps`` times contiguously via broadcast+reshape."""
    if reps == 1:
        return x
    r, c = x.shape
    return jnp.broadcast_to(x[:, None, :], (r, reps, c)).reshape(r * reps, c)


def _routed_plane(cur: jax.Array, axis_name: str, f: int,
                  perms: tuple[tuple[tuple[int, int], ...], ...]) -> jax.Array:
    """Reconstruct one level's [f, ...] stream plane edge-wise.

    The own slot never travels (every shard already holds its entity's
    stream); the other f-1 rows arrive over ``ppermute`` ring rotations,
    one hop-graph edge set per rotation.  A rotation whose (src, dst) pair
    was pruned (route-disabled at the top level) leaves that row zero —
    int16 wire words decode as invalid, exactly like a gated-off gather
    slot, so downstream masking and merges are unchanged.
    """
    plane = jnp.zeros((f,) + cur.shape, cur.dtype)
    me = jax.lax.axis_index(axis_name)
    plane = jax.lax.dynamic_update_index_in_dim(plane, cur, me, 0)
    for r, perm in enumerate(perms, start=1):
        if not perm:
            continue
        recv = jax.lax.ppermute(cur, axis_name, perm=perm)
        plane = jax.lax.dynamic_update_index_in_dim(
            plane, recv, jnp.mod(me - r, f), 0)
    return plane


def pick_exchange_mode(state, frames, plan: FabricPlan, *,
                       timing: TimedWire | None = None,
                       trials: int = 3) -> tuple[FabricPlan, dict[str, float]]:
    """Mode-selection knob: time a scanned stacked exchange under both wire
    strategies on this topology and traffic, and return the winning plan.

    ``frames`` is an ``EventFrame`` with a leading time axis (the scanned
    rounds).  Which strategy wins is topology- and gating-dependent —
    routed skips the own-subtree and route-disabled segments entirely,
    gather pays them but runs fewer, larger primitives — so callers
    autotune per plan and keep the winner (``seconds`` maps each mode to
    its best-of-``trials`` wall-clock for the record).
    """
    import time as _time

    fns = {}
    for mode in EXCHANGE_MODES:
        p = with_exchange_mode(plan, mode)

        def scanned(fr, p=p):
            def body(_, fr_t):
                out, drops = fabric_route_step(state, EventFrame(*fr_t), p,
                                               timing=timing, engine="merge")
                return None, (out.labels, out.valid, drops)
            return jax.lax.scan(body, None, tuple(fr))[1]

        fns[mode] = jax.jit(scanned)
        jax.block_until_ready(fns[mode](frames))       # compile + warm
    # Interleave the trials (A B A B ...) rather than timing each mode in a
    # block: container wall-clock drifts on the tens-of-seconds scale, and
    # interleaving puts both modes under the same drift before the per-mode
    # minimum is taken.
    seconds = dict.fromkeys(fns, float("inf"))
    for _ in range(trials):
        for mode, fn in fns.items():
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(frames))
            seconds[mode] = min(seconds[mode],
                                _time.perf_counter() - t0)
    winner = min(seconds, key=seconds.get)
    return with_exchange_mode(plan, winner), seconds


# ---------------------------------------------------------------------------
# Stacked executor: all leaves' frames on one device
# ---------------------------------------------------------------------------


def fabric_route_step(state, frames: EventFrame, plan: FabricPlan, *,
                      use_fused: bool | None = None,
                      timing: TimedWire | None = None,
                      engine: str = "auto",
                      health: FabricHealth | None = None
                      ) -> tuple[EventFrame, ExchangeDrops]:
    """One N-level hop-graph exchange round, all leaves stacked on one device.

    Args:
      state: routing state with stacked per-leaf ``fwd_tables`` /
        ``rev_tables`` (``aggregator.RouterState``; its ``route_enables``
        are ignored — enables live in the plan).
      frames: per-leaf egress frames, arrays shaped [n_nodes, cap_in].
      plan: compiled hop graph (``compile_fabric``).  Its ``exchange_mode``
        picks the merge schedule — ``"routed"`` builds each destination's
        stream from its enabled source entities only (a static edge
        schedule; needs concrete route enables) instead of gating a full
        broadcast plane, with bit-identical observables.
      use_fused: route the merge through the fused kernels (default: the
        ``REPRO_FUSED_EXCHANGE`` env flag, on).
      timing: timed datapath (``latency.timed_wire``) — ``frames.times`` are
        int32 departure timestamps and the ingress ``times`` arrivals (fixed
        per-stage path + deterministic queueing at every congested hop; each
        level-2+ crossing adds its fixed extra and uplink wait).  ``None``
        keeps the untimed wire (ingress times are zeros).
      engine: ``"auto"`` lets the plain 1-level untimed fused round take the
        original single-round Pallas kernel; ``"merge"`` forces the generic
        broadcast/merge-pack engine (same observables — used as the
        same-engine baseline by the timed benchmarks).
      health: dynamic per-edge health overlay (``FabricHealth``), traced —
        masks flows in-graph on top of the plan's static health.  Dynamic
        masking never reroutes; a masked edge loses its traffic as
        ``unroutable`` (recompile a statically degraded plan to detour).

    Returns:
      (ingress frames [n_nodes, capacity],
       ExchangeDrops(congestion, uplink, unroutable, rerouted), each
       int32[n_nodes]).

    The leaf's forward lookup and uplink pack run under the named scope
    ``leaf``, each level's pass under ``level<i>`` and the destination
    merge under ``merge``, so a profiler trace splits the exchange's device
    time by level.
    """
    if use_fused is None:
        use_fused = fused_exchange_enabled()
    if engine not in ("auto", "merge"):
        raise ValueError(f"unknown engine: {engine!r}")
    if health is not None:
        _check_health(plan, health)
    levels = plan.levels
    n, cap_in = frames.labels.shape
    if n != plan.n_nodes:
        raise ValueError(f"frames carry {n} leaf streams but the plan wires "
                         f"{plan.n_nodes}")

    routed = plan.exchange_mode == "routed"

    # Fast path: the plain 1-level star is the original fused single-round
    # kernel (bit-exact with the merge engine, pinned by the parity battery).
    if (engine == "auto" and len(levels) == 1 and timing is None and use_fused
            and levels[0].link_capacity is None and not plan.degraded
            and health is None and not routed):
        from repro.kernels.spike_router.ops import fused_exchange

        out_l, out_v, dropped = fused_exchange(
            frames.labels, frames.valid, state.fwd_tables, state.rev_tables,
            levels[0].enables, capacity=plan.capacity,
            mode=kernel_mode(True))
        ingress = EventFrame(labels=out_l, times=jnp.zeros_like(out_l),
                             valid=out_v)
        zeros = jnp.zeros_like(dropped)
        return ingress, ExchangeDrops(congestion=dropped, uplink=zeros,
                                      unroutable=zeros, rerouted=zeros)

    # The leaf's forward lookup and its uplink pack.
    with jax.named_scope("leaf"):
        wire, fwd_en = jax.vmap(routing.lookup_fwd)(state.fwd_tables,
                                                    frames.labels)
        ev = frames.valid & fwd_en                             # [n, cap_in]
        times = (_egress_times(frames.times, ev, timing)
                 if timing is not None else None)

        # Leaf uplink — pack each leaf's egress to its MGT lane capacity.
        u0 = levels[0].link_capacity
        if u0 is not None:
            packed, link_drop = make_frame(wire, times, ev, u0)
            wire, ev = packed.labels, packed.valid             # [n, u0]
            if timing is not None:
                times = packed.times
        else:
            link_drop = jnp.zeros((n,), jnp.int32)
        uplink = link_drop.astype(jnp.int32)

    layout = plan.merge_layout(cap_in)
    leaf = jnp.arange(n)
    # U_i streams, one per tier-i entity (tier 0 = leaf): labels/valid/times.
    cur_l, cur_v, cur_t = wire, ev, times
    cur_len = u0 if u0 is not None else cap_in
    gsize = 1                                 # leaves per tier-i entity
    unroutable = jnp.zeros((n,), jnp.int32)
    rerouted = jnp.zeros((n,), jnp.int32)
    recv_ok = None                            # per-leaf downlink path health
    parts_l, parts_v, parts_t, seg_lens = [], [], [], []
    for i, lvl in enumerate(levels):
        with jax.named_scope(f"level{i}"):
            f = lvl.fan_in
            gnext = gsize * f
            n_grp = n // gnext
            ent = leaf // gsize          # each leaf's entity at this level

            # Degraded mode — uplink health gates the tier-i entity streams
            # before they join this merge (and before they cascade upward):
            # detoured streams keep their merge slot (the host relays the
            # same wire content, so delivery is bit-exact) but pay the detour
            # on the timed lane; streams with no surviving route are masked
            # and their events counted unroutable, attributed to every leaf
            # of the subtree.
            dyn_up = None if health is None else health.uplink[i]
            flow_ok, live_detour = _flow_masks(lvl, dyn_up, n // gsize)
            if flow_ok is not None:
                counts = cur_v.sum(axis=-1).astype(jnp.int32)
                if timing is not None:
                    pen = _detour_penalty(lvl, timing, cur_v)
                    cur_t = jnp.where(live_detour[:, None] & cur_v,
                                      cur_t + pen, cur_t)
                cur_v = cur_v & flow_ok[:, None]
                unroutable = unroutable + jnp.where(flow_ok, 0, counts)[ent]
                rerouted = rerouted + jnp.where(live_detour, counts, 0)[ent]
            # Downlink health accumulates along each leaf's descent path:
            # the level-i part reaches a destination through its downlinks at
            # levels i..1, so a dead edge kills this and every higher part.
            dyn_down = None if health is None else health.downlink[i]
            d_ok = _down_mask(lvl, dyn_down, ent)
            if d_ok is not None:
                recv_ok = d_ok if recv_ok is None else recv_ok & d_ok

            s_len = f * cur_len
            anc = leaf // gnext          # tier-(i+1) ancestor of each leaf
            if routed:
                # Routed mode: only the hop-graph edges enter the merge —
                # each destination selects its enabled source entities'
                # streams via a static per-level schedule (padded to the max
                # in-degree with all-invalid segments), so the own subtree and
                # route-disabled pairs cost no merge work instead of riding
                # along gated-off.  The selection moves int16 wire words
                # (validity rides the embedded bit; the enable lane is a
                # static constant) and keeps ascending source order, matching
                # the gather layout — the surviving valid-event sequence, and
                # with it labels/valids/drops/timestamps, is bit-exact.
                src_flat, live, deg = _routed_leaf_maps(lvl.enables, i, n,
                                                        gsize, f)
                n_ent = n_grp * f
                sel = pack_wire16(cur_l, cur_v).reshape(n_grp, f, cur_len)
                sel = sel[:, src_flat].reshape(n_ent, deg * cur_len)
                # Entity → leaf expansion is a contiguous repeat (leaves of
                # one entity are adjacent), so it lowers to broadcast+reshape
                # — a copy loop, never a gather chain XLA would re-evaluate
                # element-wise inside the merge fusion.
                part_l = _repeat_rows(sel, n // n_ent)
                part_v = jnp.broadcast_to(
                    live[:, :, None],
                    (n, deg, cur_len)).reshape(n, deg * cur_len)
                per_child = layout[i][:len(layout[i]) // f]
                level_segs = list(per_child) * deg
            else:
                # S_i per tier-(i+1) entity: the concat of its children's U_i.
                s_l = cur_l.reshape(n_grp, s_len)
                s_v = cur_v.reshape(n_grp, f, cur_len)
                child = ent % f               # leaf's child slot at this level
                gate = lvl.enables.T[child]   # [n, f] src child → this dest
                if i > 0:
                    gate = gate & (jnp.arange(f)[None, :] != child[:, None])
                if n_grp == 1:
                    # Top-of-tree streams stay shared views (the hardware
                    # broadcasts a wire, not a buffer); only validity is
                    # per-destination.
                    part_l = jnp.broadcast_to(s_l.reshape(1, s_len),
                                              (n, s_len))
                    part_v = (s_v[0][None]
                              & gate[:, :, None]).reshape(n, s_len)
                else:
                    part_l = s_l[anc]
                    part_v = (s_v[anc] & gate[:, :, None]).reshape(n, s_len)
                level_segs = list(layout[i])
            if recv_ok is not None:
                if routed:
                    # The enable lane is slots, not events — count the
                    # embedded valid bits for the loss attribution, like the
                    # sharded path.
                    _, w_v = unpack_wire16(part_l)
                    lost = (w_v & part_v).sum(axis=-1).astype(jnp.int32)
                else:
                    lost = part_v.sum(axis=-1).astype(jnp.int32)
                part_v = part_v & recv_ok[:, None]
                unroutable = unroutable + jnp.where(recv_ok, 0, lost)
            parts_l.append(part_l)
            parts_v.append(part_v)
            if timing is not None:
                if routed:
                    sel_t = cur_t.reshape(n_grp, f, cur_len)
                    sel_t = sel_t[:, src_flat].reshape(n_ent, deg * cur_len)
                    parts_t.append(_repeat_rows(sel_t, n // n_ent))
                else:
                    s_t = cur_t.reshape(n_grp, s_len)
                    parts_t.append(
                        jnp.broadcast_to(s_t.reshape(1, s_len), (n, s_len))
                        if n_grp == 1 else s_t[anc])
            seg_lens += level_segs

            if i + 1 < len(levels):
                # Prepare U_{i+1}: each tier-(i+1) entity uplinks its
                # aggregated stream into the next level's merge — timed
                # events pay the crossing extra plus the wait of their rank
                # in the stream, and the pack cascades (an event crossing k
                # levels must survive every intermediate uplink).  The
                # cascade is ungated — it aggregates whole entity streams —
                # so routed mode feeds it the same full concatenation as
                # gather.
                nxt = levels[i + 1]
                s_l = cur_l.reshape(n_grp, s_len)
                s_vf = cur_v.reshape(n_grp, s_len)
                if timing is not None:
                    okp = s_vf.astype(jnp.int32)
                    prank = jnp.cumsum(okp, axis=-1) - okp
                    extra = (nxt.extra_ns if nxt.extra_ns is not None
                             else timing.second_layer_extra_ns)
                    s_t = jnp.where(
                        s_vf, cur_t.reshape(n_grp, s_len) + extra
                        + _queue_wait_i32(prank, timing.uplink_queue), 0)
                else:
                    s_t = None
                if nxt.link_capacity is not None:
                    up, drop = make_frame(s_l, s_t, s_vf, nxt.link_capacity)
                    cur_l, cur_v = up.labels, up.valid
                    cur_t = up.times if timing is not None else None
                    cur_len = nxt.link_capacity
                    uplink = uplink + drop[anc].astype(jnp.int32)
                else:
                    cur_l, cur_v, cur_t = s_l, s_vf, s_t
                    cur_len = s_len
                gsize = gnext

    # The destination merge of every level's parts.
    with jax.named_scope("merge"):
        labels = jnp.concatenate(parts_l, axis=-1)
        valid = jnp.concatenate(parts_v, axis=-1)
        merge_times = (jnp.concatenate(parts_t, axis=-1)
                       if timing is not None else None)
        seg_lens = tuple(seg_lens)
        if routed and not (use_fused or timing is not None):
            # The plain-pack fallback wants unpacked labels; the fused/timed
            # merges take the int16 wire words (embedded valid & enable lane)
            # directly, like the sharded executor.
            w_l, w_v = unpack_wire16(labels)
            labels, valid = w_l, w_v & valid
        if use_fused or timing is not None:
            ingress, dropped = _fused_merge(
                labels, valid, state.rev_tables, plan.capacity,
                seg_lens=seg_lens, compact=plan.compact, timing=timing,
                use_fused=use_fused, times=merge_times)
            return ingress, ExchangeDrops(congestion=dropped, uplink=uplink,
                                          unroutable=unroutable,
                                          rerouted=rerouted)
        mixed, dropped = make_frame_segmented(labels, None, valid,
                                              plan.capacity, seg_lens,
                                              compact=plan.compact)
        chip, rev_en = jax.vmap(routing.lookup_rev)(state.rev_tables,
                                                    mixed.labels)
        out_valid = mixed.valid & rev_en
        ingress = EventFrame(labels=jnp.where(out_valid, chip, 0),
                             times=mixed.times, valid=out_valid)
        return ingress, ExchangeDrops(congestion=dropped, uplink=uplink,
                                      unroutable=unroutable, rerouted=rerouted)


# ---------------------------------------------------------------------------
# Sharded executor: call inside shard_map, one leaf per mesh slice
# ---------------------------------------------------------------------------


def fabric_exchange(frame: EventFrame, axis_names: tuple[str, ...],
                    fwd_table: jax.Array, rev_table: jax.Array,
                    plan: FabricPlan, *, use_fused: bool | None = None,
                    timing: TimedWire | None = None,
                    health: FabricHealth | None = None
                    ) -> tuple[EventFrame, ExchangeDrops]:
    """One N-level exchange round from the perspective of a single leaf shard.

    Must run inside ``shard_map`` on a nested mesh with one axis per level,
    ``axis_names`` leaf level first (see ``parallel.sharding.fabric_mesh``).
    Each level does one ``all_gather`` along its axis — level 1 is the
    backplane star, level 2 the second-layer node, level 3 the extension
    chain, ... — with the gathered stream optionally packed to the next
    level's ``link_capacity`` before uplinking (packs cascade).  All gathers
    move int16 wire words (``events.pack_wire16``); the timed lane, when
    enabled, travels as a separate int32 plane.  Gating, segment layout,
    drops and timestamps mirror ``fabric_route_step`` bit-exactly — a
    degraded plan masks dead slots on the gathered planes (a dead link
    still clocks its gather; the words are zeroed, i.e. invalid) and
    retimes detoured streams identically.  ``health`` is the dynamic
    overlay; under ``shard_map`` pass it as replicated constants.

    A ``"routed"`` plan replaces each level's broadcast gather with
    ``ppermute`` neighbor exchanges along the hop-graph edges
    (``_routed_plane``): the own slot never travels, and at the top level
    route-disabled (src, dst) pairs are pruned from the rotation schedule
    entirely (``parallel.sharding.edge_neighbor_permutes``) — non-top
    levels keep full rotations because the ungated uplink cascade
    aggregates whole entity streams.  Unreceived rows stay zero, which
    decodes as invalid — the same observables as a gated-off gather slot.
    """
    if use_fused is None:
        use_fused = fused_exchange_enabled()
    routed = plan.exchange_mode == "routed"
    if routed:
        from repro.parallel.sharding import edge_neighbor_permutes
    levels = plan.levels
    if len(axis_names) != len(levels):
        raise ValueError(f"{len(axis_names)} mesh axes for "
                         f"{len(levels)} fabric levels")
    if health is not None:
        _check_health(plan, health)
    degraded = plan.degraded or health is not None
    cap_in = frame.labels.shape[-1]

    # The leaf's forward lookup and its uplink pack.
    with jax.named_scope("leaf"):
        wire, fwd_en = routing.lookup_fwd(fwd_table, frame.labels)
        ev = frame.valid & fwd_en
        times = (_egress_times(frame.times, ev, timing)
                 if timing is not None else None)
        u0 = levels[0].link_capacity
        if u0 is not None:
            packed, uplink = make_frame(wire, times, ev, u0)
            wire, ev = packed.labels, packed.valid
            if timing is not None:
                times = packed.times
        else:
            uplink = jnp.zeros((), jnp.int32)

    if degraded:
        # This shard's global leaf index, from the per-level coordinates.
        from repro.parallel.sharding import fabric_leaf_index

        leaf = fabric_leaf_index(axis_names,
                                 tuple(lvl.fan_in for lvl in levels))
    unroutable = jnp.zeros((), jnp.int32)
    rerouted = jnp.zeros((), jnp.int32)
    recv_ok = None

    layout = plan.merge_layout(cap_in)
    cur_words = pack_wire16(wire, ev)
    cur_times = times
    gsize = 1
    parts_w, parts_en, parts_t, seg_lens = [], [], [], []
    for i, lvl in enumerate(levels):
        with jax.named_scope(f"level{i}"):
            f = lvl.fan_in
            if degraded:
                # Every leaf of a tier-i entity redundantly carries the entity
                # stream, so per-leaf attribution mirrors the stacked executor:
                # count this entity's (pre-mask) events against my own leaf.
                ent_me = leaf // gsize
                dyn_up = None if health is None else health.uplink[i]
                flow_ok, live_detour = _flow_masks(lvl, dyn_up,
                                                   plan.n_nodes // gsize)
                if flow_ok is not None:
                    _, my_v = unpack_wire16(cur_words)
                    my_count = my_v.sum().astype(jnp.int32)
                    unroutable = unroutable + jnp.where(flow_ok[ent_me], 0,
                                                        my_count)
                    rerouted = rerouted + jnp.where(live_detour[ent_me],
                                                    my_count, 0)
                dyn_down = None if health is None else health.downlink[i]
                d_ok = _down_mask(lvl, dyn_down, ent_me)
                if d_ok is not None:
                    recv_ok = d_ok if recv_ok is None else recv_ok & d_ok
            else:
                flow_ok = None
            if routed:
                perms = edge_neighbor_permutes(
                    _concrete_enables(lvl.enables),
                    prune=(i + 1 == len(levels)))
                g_words = _routed_plane(cur_words, axis_names[i], f, perms)
                g_times = (_routed_plane(cur_times, axis_names[i], f, perms)
                           if timing is not None else None)
            else:
                g_words = jax.lax.all_gather(cur_words, axis_names[i], axis=0)
                g_times = (jax.lax.all_gather(cur_times, axis_names[i], axis=0)
                           if timing is not None else None)
            me = jax.lax.axis_index(axis_names[i])
            if flow_ok is not None:
                # Gathered slot s holds the entity (leaf // gnext) * f + s.
                slots = (leaf // (gsize * f)) * f + jnp.arange(f)
                flow_s = flow_ok[slots]
                if timing is not None:
                    _, g_v = unpack_wire16(g_words)
                    pen = _detour_penalty(lvl, timing, g_v)
                    g_times = jnp.where(live_detour[slots][:, None] & g_v,
                                        g_times + pen, g_times)
                    g_times = jnp.where(flow_s[:, None], g_times, 0)
                g_words = jnp.where(flow_s[:, None], g_words, 0)
            gate = lvl.enables[:, me]                       # [f]
            if i > 0:
                gate = gate & (jnp.arange(f) != me)
            en = jnp.broadcast_to(gate[:, None], g_words.shape).reshape(-1)
            if recv_ok is not None:
                _, g_v = unpack_wire16(g_words.reshape(-1))
                lost = (g_v & en).sum().astype(jnp.int32)
                unroutable = unroutable + jnp.where(recv_ok, 0, lost)
                en = en & recv_ok
            parts_w.append(g_words.reshape(-1))
            parts_en.append(en)
            if timing is not None:
                parts_t.append(g_times.reshape(-1))
            seg_lens += list(layout[i])
            gsize = gsize * f

            if i + 1 < len(levels):
                nxt = levels[i + 1]
                s_words = g_words.reshape(-1)
                s_labels, s_valid = unpack_wire16(s_words)
                if timing is not None:
                    okp = s_valid.astype(jnp.int32)
                    prank = jnp.cumsum(okp) - okp
                    extra = (nxt.extra_ns if nxt.extra_ns is not None
                             else timing.second_layer_extra_ns)
                    wait = _queue_wait_i32(prank, timing.uplink_queue)
                    s_t = jnp.where(s_valid,
                                    g_times.reshape(-1) + extra + wait, 0)
                else:
                    s_t = None
                if nxt.link_capacity is not None:
                    up, drop = make_frame(s_labels, s_t, s_valid,
                                          nxt.link_capacity)
                    cur_words = pack_wire16(up.labels, up.valid)
                    cur_times = up.times if timing is not None else None
                    uplink = uplink + drop
                else:
                    cur_words = s_words
                    cur_times = s_t

    # The destination merge of every level's parts.
    with jax.named_scope("merge"):
        flat_words = jnp.concatenate(parts_w)
        flat_en = jnp.concatenate(parts_en)
        flat_times = (jnp.concatenate(parts_t) if timing is not None else None)
        seg_lens = tuple(seg_lens)
        if use_fused or timing is not None:
            ingress, dropped = _fused_merge(
                flat_words, flat_en, rev_table, plan.capacity,
                seg_lens=seg_lens, compact=plan.compact, timing=timing,
                use_fused=use_fused, times=flat_times)
            return ingress, ExchangeDrops(congestion=dropped, uplink=uplink,
                                          unroutable=unroutable,
                                          rerouted=rerouted)
        g_labels, g_valid = unpack_wire16(flat_words)
        mixed, dropped = make_frame_segmented(g_labels, None,
                                              g_valid & flat_en,
                                              plan.capacity, seg_lens,
                                              compact=plan.compact)
        chip, rev_en = routing.lookup_rev(rev_table, mixed.labels)
        out_valid = mixed.valid & rev_en
        ingress = EventFrame(labels=jnp.where(out_valid, chip, 0),
                             times=mixed.times, valid=out_valid)
        return ingress, ExchangeDrops(congestion=dropped, uplink=uplink,
                                      unroutable=unroutable, rerouted=rerouted)


# ---------------------------------------------------------------------------
# Mesh binding: N nested axes, one per level
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FabricInterconnect:
    """Builds shard_map'd N-level exchange functions over a nested mesh.

    One mesh axis per fabric level, innermost (fastest) axis = level 1 —
    ``parallel.sharding.fabric_mesh(plan)`` constructs a matching mesh.
    ``axis_names`` lists them leaf level first; ``None`` derives them from
    the mesh (reversed axis order, outermost = top level).

    ``exchange_fn()`` dispatches one round; ``stream_fn()`` scans T rounds
    inside a single ``shard_map`` with the routing tables hoisted to loop
    invariants.  Unlike the legacy ``StarInterconnect``, route enables come
    from the plan, so the returned functions take only
    ``(frames, fwd_tables, rev_tables)``.
    """

    mesh: jax.sharding.Mesh
    plan: FabricPlan
    axis_names: tuple[str, ...] | None = None
    use_fused: bool | None = None
    timing: TimedWire | None = None
    health: FabricHealth | None = None  # dynamic overlay, closed over
    #                                     (replicated constants per round)

    def _axes(self) -> tuple[str, ...]:
        axes = (tuple(self.axis_names) if self.axis_names is not None
                else tuple(reversed(self.mesh.axis_names)))
        if len(axes) != self.plan.n_levels:
            raise ValueError(f"{len(axes)} mesh axes for "
                             f"{self.plan.n_levels} fabric levels")
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        for name, lvl in zip(axes, self.plan.levels):
            if sizes.get(name) != lvl.fan_in:
                raise ValueError(
                    f"mesh axis {name!r} has size {sizes.get(name)} but the "
                    f"fabric level expects fan_in {lvl.fan_in}")
        return axes

    def _round(self):
        axes = self._axes()
        plan, fused, timing = self.plan, self.use_fused, self.timing
        health = self.health

        def round_fn(frame, fwd, rev):
            return fabric_exchange(frame, axes, fwd[0], rev[0], plan,
                                   use_fused=fused, timing=timing,
                                   health=health)

        from jax.sharding import PartitionSpec as P

        shard = P(tuple(reversed(axes)))          # top level outermost
        return round_fn, shard, (shard, shard)

    def exchange_fn(self, *, donate: bool = False):
        """One-round dispatch ``fn(frame, fwd_tables, rev_tables)``.

        ``donate=True`` marks the input frame's wire buffers as donated to
        the jit call — the exchange may reuse their device memory for its
        outputs (the caller's frame is consumed; don't reference it after
        the call).  Opt-in because callers that re-dispatch the same frame
        (timing loops, checkpoint replays) must keep their buffers alive.
        On CPU donation is a no-op (XLA ignores it with a warning
        suppressed by jax), so the flag only changes peak memory where an
        accelerator backend is attached.
        """
        round_fn, shard, table_specs = self._round()

        def fn(frame, *tables):
            out, drops = round_fn(jax.tree.map(lambda x: x[0], frame),
                                  *tables)
            return (jax.tree.map(lambda x: x[None], out),
                    jax.tree.map(lambda x: x[None], drops))

        in_specs = (EventFrame(shard, shard, shard), *table_specs)
        out_specs = (EventFrame(shard, shard, shard),
                     ExchangeDrops(shard, shard, shard, shard))
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs),
                       donate_argnums=(0,) if donate else ())

    def stream_fn(self, *, donate: bool = False):
        """Scan T rounds inside one ``shard_map`` (leading time axis).

        ``donate=True`` donates the T-step input frame stack to the call
        (see ``exchange_fn``); the scan carry's wire buffers are donated by
        XLA's loop lowering regardless — this flag extends that to the
        caller-visible frame planes."""
        from jax.sharding import PartitionSpec as P

        round_fn, shard, table_specs = self._round()

        def fn(frames, *tables):
            frames = jax.tree.map(lambda x: x[:, 0], frames)

            def body(_, fr):
                return None, round_fn(fr, *tables)

            _, (outs, drops) = jax.lax.scan(body, None, frames)
            return (jax.tree.map(lambda x: x[:, None], outs),
                    jax.tree.map(lambda x: x[:, None], drops))

        tshard = P(None, *shard)
        in_specs = (EventFrame(tshard, tshard, tshard), *table_specs)
        out_specs = (EventFrame(tshard, tshard, tshard),
                     ExchangeDrops(tshard, tshard, tshard, tshard))
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs),
                       donate_argnums=(0,) if donate else ())
