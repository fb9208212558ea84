"""Fused exchange datapath — the paper's §III routing around a Pallas pack unit.

Per exchange round the hardware does: fwd LUT (BRAM 16→16 lookup, one output
bit is the routing enable) → enable masking → Aggregator star broadcast with
static per-route enables → capacity-bounded pack (prefix-sum pack unit,
congestion drop + count) → rev LUT (15→17) at the receiving Node-FPGA.

The pack unit is the kernel (``_pack_kernel``); the LUT lookups around it
are XLA gathers, which the TPU runs natively (Mosaic lowers no gather from
a 2^15/2^16-entry table).  Four wrappers drive the one kernel:

``spike_router_fwd``      fwd LUT + mask + pack for each node's egress.
``exchange_fwd``          the whole round, batched over destinations: the
                          fwd LUTs run once on the shared per-source stream
                          (never copied per destination — the kernel maps
                          one shared payload block into every grid cell),
                          each destination packs with its own enable mask,
                          then its own rev LUT.  Used by ``route_step`` and
                          the plain-star fast path of the fabric executor.
``exchange_stream_fwd``   T rounds in one program: ``exchange_fwd`` vmapped
                          over the timestep (a leading grid axis).
``merge_pack_fwd``        merge + pack + rev LUT for already-fwd-routed
                          streams; the rev LUT may be shared across the
                          batch or per-row (stacked hierarchical routing);
                          the stream may arrive as int16 wire words
                          (``events.pack_wire16``), unpacked in-kernel; an
                          int32 timestamp lane may ride the pack and pick up
                          the destination's rank-dependent queueing.  Used
                          by every merge of ``repro.core.fabric``.

The bare pack, with no LUT, is ``_pack_call`` itself; ``ops.pack_frame``
runs it for the chips' egress frames.

Kernel layout: each grid cell packs ``ROWS`` = 8 streams (one sublane tile),
the batch padded to a multiple of 8; the event axis is padded to a multiple
of ``TILE`` = 128 with invalid slots (which never change a rank).  In VMEM
the cell's streams are transposed to columns (events on sublanes):

    rank   = strictly-lower-triangular 0/1 matmul per 128-event tile
             (bf16 operands are exact for 0/1, f32 accumulation is exact
             below 2^24) + a running base over the tiles' totals
    keep   = ok & rank < capacity            (overflow → drop counter)
    out[c] = Σ_i [rank_i == c] · payload_i   (one-hot compare + int32
                                              select-and-reduce; each slot
                                              has at most one writer)

Arrival order is preserved, overflow events are dropped and counted, and
invalid output slots are zero-filled — bit-exact with the jnp oracles in
``ref.py`` (labels, valid, timestamps, drop counts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bit layout of the LUT entries is owned by repro.core.routing (the table
# builders); the 16-bit wire-word layout by repro.core.events; the timed
# lane's queue arithmetic by repro.core.latency.  The kernels decode/compute
# with the same constants and helpers.
from repro.core.events import WIRE_VALID_BIT
from repro.core.latency import queue_wait_i32
from repro.core.routing import (CHIP_LABEL_MASK as CHIP_MASK,
                                FWD_ENABLE_BIT as ENABLE_BIT,
                                REV_ENABLE_BIT, WIRE_LABEL_MASK as WIRE_MASK)

ROWS = 8      # streams per grid cell: one sublane tile
TILE = 128    # events per grid step: one lane tile / MXU pass


def _exclusive_rank(ok: jax.Array) -> jax.Array:
    """Exclusive prefix sums of 0/1 ``ok`` along the last axis as one
    strictly-upper-triangular matmul — exact: 0/1 in bf16, sums in f32
    below 2^24."""
    n = ok.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tri = (i < j).astype(jnp.bfloat16)
    rank = jnp.dot(ok.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32)
    return rank.astype(jnp.int32)


def _segment_indices(ok: jax.Array, base: jax.Array, capacity: int):
    """Write-set of one pack-unit tile: ``ok`` [..., seg_len] 0/1 int32,
    ``base`` [..., 1] the events offered before the tile.  Returns ``(idx,
    keep)``: kept events go to their global arrival rank, rejected ones are
    parked at index ``capacity``, which no output slot matches."""
    pos = _exclusive_rank(ok) + base
    keep = (ok == 1) & (pos < capacity)
    return jnp.where(keep, pos, capacity), keep


def _pack_segmented_indices(ok: jax.Array, capacity: int):
    """Write-set of the pack unit over ``ok`` [n_seg, ..., seg_len]: the
    tiles in sequence (the kernel's sequential grid axis), each ranked
    within itself on top of a running base of the earlier tiles' totals —
    ``base[seg] + within`` is exactly the global arrival rank.  Returns
    ``(idx, keep)`` on the concatenated stream [..., n_seg·seg_len].

    Factored out so the static kernel checker
    (``repro.analysis.kernelcheck``) proves in-bounds/disjointness on the
    index arithmetic the kernel runs.
    """
    base = jnp.zeros((*ok.shape[1:-1], 1), jnp.int32)
    idx, keep = [], []
    for seg in ok:
        i, k = _segment_indices(seg, base, capacity)
        idx.append(i)
        keep.append(k)
        base = base + jnp.sum(seg, axis=-1, keepdims=True)
    return jnp.concatenate(idx, axis=-1), jnp.concatenate(keep, axis=-1)


def _pack_indices(ok: jax.Array, capacity: int):
    """Write-set of the global (one-tile) pack unit: ``ok`` [..., n]."""
    return _pack_segmented_indices(ok[None], capacity)


def _pack_tile(ok: jax.Array, lanes, base: jax.Array, capacity: int):
    """One grid step of the pack unit on ``r`` streams.

    ok: [r, seg_len] 0/1; lanes: payloads [r, seg_len] (wire labels[,
    timestamps]); base: [r, 1] events offered before this tile.  Returns
    (the tile's contribution to every lane's packed output and then to the
    valid mask, each [r, capacity] — zero outside its kept events' slots —,
    the next base).  Compaction is a one-hot compare of the rank against the
    output slot plus an int32 select-and-reduce over the events (sublanes,
    after a transpose): kept events write distinct slots, so each sum is
    the one writer's value, and tiles add up exactly.
    """
    idx, keep = _segment_indices(ok, base, capacity)
    at = idx.T                                          # [seg_len, r]
    cols = [lane.T for lane in lanes] + [keep.astype(jnp.int32).T]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, capacity), 1)
    rows = [[] for _ in cols]
    for r in range(ok.shape[0]):
        hit = at[:, r:r + 1] == slot                    # [seg_len, capacity]
        for out, col in zip(rows, cols):
            out.append(jnp.sum(jnp.where(hit, col[:, r:r + 1], 0), axis=0,
                               keepdims=True))
    return ([jnp.concatenate(out, axis=0) for out in rows],
            base + jnp.sum(ok, axis=-1, keepdims=True))


def _pack_segmented(ok: jax.Array, payload: jax.Array, capacity: int,
                    payload2: jax.Array | None = None):
    """The pack unit on one stream split into tiles — the kernel's grid
    steps in sequence.

    ok, payload (and ``payload2``, the timed datapath's timestamp lane):
    [n_seg, seg_len] int32.  Returns (packed_payload [capacity],
    [packed_payload2 [capacity],] packed_valid [capacity], dropped scalar) —
    arrival order preserved, overflow dropped and counted, empty slots 0.
    """
    lanes = [payload] + ([] if payload2 is None else [payload2])
    acc = [jnp.zeros((1, capacity), jnp.int32) for _ in range(len(lanes) + 1)]
    base = jnp.zeros((1, 1), jnp.int32)
    for seg in range(ok.shape[0]):
        contrib, base = _pack_tile(ok[seg][None],
                                   [lane[seg][None] for lane in lanes], base,
                                   capacity)
        acc = [a + c for a, c in zip(acc, contrib)]
    dropped = base[0, 0] - jnp.sum(acc[-1])
    return (*(a[0] for a in acc), dropped)


def _pack(ok: jax.Array, payload: jax.Array, capacity: int,
          payload2: jax.Array | None = None):
    """The global (one-tile) pack unit on one stream: ``ok``/``payload``
    [n] (see ``_pack_segmented``)."""
    return _pack_segmented(
        ok[None], payload[None], capacity,
        payload2=None if payload2 is None else payload2[None])


def _dest_queue_ns(capacity: int, queue: tuple[int, int, int]) -> jax.Array:
    """Destination-side queueing delay by pack rank (== output slot index),
    as a [1, capacity] row.

    ``queue`` is the static (service_ns, cc_interval, stall_total_ns) triple
    from ``latency.TimedWire.queue``: the event at output slot ``r`` waited
    ``r·service + ⌊r/cc⌋·stall_total`` behind its merged predecessors —
    ``latency.queue_wait_i32`` (the integer twin of
    ``latency.hop_delays(...).total_ns``) evaluated on the slot index.
    """
    rank = jax.lax.broadcasted_iota(jnp.int32, (1, capacity), 1)
    return queue_wait_i32(rank, queue)


def _pack_kernel(ok_ref, payload_ref, *refs, capacity: int, wire16: bool,
                 queue: tuple[int, int, int] | None):
    """One grid step: ``ROWS`` streams × one ``TILE`` of their events.

    Grid (row block, tile): the tile axis is sequential, and the output
    blocks stay resident across it as accumulators; the drop block doubles
    as the running count of offered events (the rank base) until the last
    tile turns it into the drop count.

    ok: [ROWS, TILE] 0/1; payload: [ROWS, TILE], or [1, TILE] shared by
    every row (the exchange's per-source stream, one per destination's
    enable mask).  ``wire16``: the payload carries int16 wire words (15-bit
    label, valid flag in bit 15, as emitted by ``events.pack_wire16``) —
    the word is unpacked here and its valid bit ANDed with ``ok``.  Timed
    datapath (``queue`` set): an int32 timestamp lane (``times_ref``) rides
    the pack and picks up the destination queueing of its arrival rank
    (``_dest_queue_ns``).  Ref order: (ok, payload[, times] | out_payload,
    [out_times,] out_valid, dropped).
    """
    if queue is not None:
        times_ref, *out_refs, drop_ref = refs
    else:
        times_ref = None
        *out_refs, drop_ref = refs
    tile = pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        for ref in (*out_refs, drop_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.int32)

    ok = ok_ref[...]
    payload = jnp.broadcast_to(payload_ref[...], ok.shape)
    if wire16:
        word = payload & 0xFFFF
        ok = ok * ((word >> WIRE_VALID_BIT) & 1)
        payload = word & WIRE_MASK
    lanes = [payload] + ([] if times_ref is None else [times_ref[...]])
    contrib, drop_ref[...] = _pack_tile(ok, lanes, drop_ref[...], capacity)
    for ref, c in zip(out_refs, contrib):
        ref[...] += c

    @pl.when(tile == pl.num_programs(1) - 1)
    def _finish():
        out_v_ref = out_refs[-1]
        valid = out_v_ref[...]
        drop_ref[...] -= jnp.sum(valid, axis=1, keepdims=True)
        if queue is not None:
            # Arrival time = departure + accumulated fixed path (already in
            # the lane) + this destination's rank-dependent queueing.
            out_t_ref = out_refs[1]
            out_t_ref[...] = jnp.where(
                valid == 1, out_t_ref[...] + _dest_queue_ns(capacity, queue),
                0)


def _pack_call(ok: jax.Array, payload: jax.Array, *, capacity: int,
               interpret: bool, times: jax.Array | None = None,
               queue: tuple[int, int, int] | None = None,
               wire16: bool = False):
    """Run the pack kernel over a batch of streams.

    ok: int32[b, n] 0/1; payload: int32[b, n], or int32[1, n] shared by all
    b streams; ``times`` (int32[b, n]) with ``queue`` adds the timed lane.
    The batch is padded to a multiple of ``ROWS`` and the events to a
    multiple of ``TILE`` with invalid slots.
    Returns (packed_payload i32[b, capacity], [packed_times i32[b,
    capacity],] packed_valid i32[b, capacity], dropped i32[b]).
    """
    b, n = ok.shape
    rows = -(-b // ROWS) * ROWS
    width = max(1, -(-n // TILE)) * TILE      # ≥ 1 tile: outputs initialized
    shared = payload.shape[0] != b

    def pad(x, r):
        return jnp.pad(x.astype(jnp.int32),
                       ((0, r - x.shape[0]), (0, width - n)))

    row_spec = pl.BlockSpec((ROWS, TILE), lambda i, t: (i, t))
    pay_spec = (pl.BlockSpec((1, TILE), lambda i, t: (0, t)) if shared
                else row_spec)
    out_spec = pl.BlockSpec((ROWS, capacity), lambda i, t: (i, 0))
    drop_spec = pl.BlockSpec((ROWS, 1), lambda i, t: (i, 0))
    out_lane = jax.ShapeDtypeStruct((rows, capacity), jnp.int32)
    n_lanes = 2 if times is None else 3

    operands = [pad(ok, rows), pad(payload, 1 if shared else rows)]
    in_specs = [row_spec, pay_spec]
    if times is not None:
        operands.append(pad(times, rows))
        in_specs.append(row_spec)
    kernel = functools.partial(_pack_kernel, capacity=capacity,
                               wire16=wire16, queue=queue)
    # The kernel's work, for XLA's scheduler: without it the call looks free,
    # and no prefetch is overlapped with it (the fabric's 25 MB forward LUT
    # then stays in HBM behind the egress pack).  Per event: the rank matmul
    # over its tile, and per output slot one compare plus a select and an
    # add for each output lane.
    cost = pl.CostEstimate(
        flops=rows * width * (2 * TILE + capacity * (1 + 2 * n_lanes)),
        transcendentals=0,
        bytes_accessed=4 * (sum(o.size for o in operands)
                            + rows * (n_lanes * capacity + 1)))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // ROWS, width // TILE),
        in_specs=in_specs,
        out_specs=(out_spec,) * n_lanes + (drop_spec,),
        out_shape=(out_lane,) * n_lanes
        + (jax.ShapeDtypeStruct((rows, 1), jnp.int32),),
        # The tile axis carries the accumulators: sequential ("arbitrary").
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=cost,
        # One name for every caller, so the profiler names the kernel alike.
        name="spike_router_pack",
    )(*operands)
    return (*(o[:b] for o in outs[:-1]), outs[-1][:b, 0])


def _rev_lookup(rev: jax.Array, packed: jax.Array, packed_valid: jax.Array):
    """rev LUT at the receiving node (XLA gather): shared [2^15] or one
    table per stream [b, 2^15].  Rev-disabled events keep their slot but are
    invalidated silently (not counted as congestion drops) — §III."""
    wire = packed & WIRE_MASK
    if rev.ndim == 1:
        entry = jnp.take(rev, wire, axis=0)
    else:
        entry = jnp.take_along_axis(rev, wire, axis=1)
    out_v = packed_valid * ((entry >> REV_ENABLE_BIT) & 1)
    return jnp.where(out_v == 1, entry & CHIP_MASK, 0), out_v


def spike_router_fwd(labels: jax.Array, valid: jax.Array, lut: jax.Array, *,
                     capacity: int, interpret: bool = False):
    """Egress only: fwd LUT + enable mask + pack.

    labels, valid: int32[batch, n_events]; lut: int32[65536].
    Returns (out_labels i32[batch, capacity], out_valid i32[batch, capacity],
             dropped i32[batch, 1]).
    """
    entry = jnp.take(lut, labels & CHIP_MASK, axis=0)
    ok = valid * ((entry >> ENABLE_BIT) & 1)
    out_l, out_v, dropped = _pack_call(ok, entry & WIRE_MASK,
                                       capacity=capacity, interpret=interpret)
    return out_l, out_v, dropped[:, None]


def exchange_fwd(labels: jax.Array, valid: jax.Array, fwd_luts: jax.Array,
                 rev_luts: jax.Array, enables: jax.Array, *,
                 capacity: int, interpret: bool = False):
    """Full round: fwd LUT → enable → merge → pack → rev LUT, batched over
    destinations.

    labels, valid: int32[n_src, cap_in] (shared across destinations);
    fwd_luts: int32[n_src, 2^16]; rev_luts: int32[n_dst, 2^15];
    enables: int32[n_src, n_dst].  The merge is src-major (arrival order).
    Returns (out_labels i32[n_dst, capacity], out_valid i32[n_dst, capacity],
             dropped i32[n_dst, 1]).
    """
    n_src, cap_in = labels.shape
    entry = jnp.take_along_axis(fwd_luts, labels & CHIP_MASK, axis=1)
    sent = valid * ((entry >> ENABLE_BIT) & 1)           # [n_src, cap_in]
    # Per-destination enable mask over the shared src-major stream.
    ok = (enables.T[:, :, None] * sent[None]).reshape(-1, n_src * cap_in)
    wire = (entry & WIRE_MASK).reshape(1, n_src * cap_in)
    packed, packed_v, dropped = _pack_call(ok, wire, capacity=capacity,
                                           interpret=interpret)
    out_l, out_v = _rev_lookup(rev_luts, packed, packed_v)
    return out_l, out_v, dropped[:, None]


def exchange_stream_fwd(labels: jax.Array, valid: jax.Array,
                        fwd_luts: jax.Array, rev_luts: jax.Array,
                        enables: jax.Array, *, capacity: int,
                        interpret: bool = False):
    """T full rounds in one program: ``exchange_fwd`` over a leading
    timestep grid axis (routing tables are configuration, shared by every
    step).

    labels, valid: int32[T, n_src, cap_in] per-timestep egress frames;
    fwd_luts: int32[n_src, 2^16]; rev_luts: int32[n_dst, 2^15];
    enables: int32[n_src, n_dst].
    Returns (out_labels i32[T, n_dst, capacity],
             out_valid i32[T, n_dst, capacity], dropped i32[T, n_dst]).
    """
    step = functools.partial(exchange_fwd, capacity=capacity,
                             interpret=interpret)
    out_l, out_v, dropped = jax.vmap(step, in_axes=(0, 0, None, None, None))(
        labels, valid, fwd_luts, rev_luts, enables)
    return out_l, out_v, dropped[..., 0]


def merge_pack_fwd(labels: jax.Array, valid: jax.Array, rev_lut: jax.Array, *,
                   capacity: int, interpret: bool = False,
                   times: jax.Array | None = None,
                   queue: tuple[int, int, int] | None = None):
    """Merge + pack + rev LUT over a batch of pre-routed streams.

    labels, valid: [batch, n_events] wire labels (fwd LUT already applied,
    route enables already folded into ``valid``).  ``labels`` is int32 wire
    labels, or int16 wire words (``events.pack_wire16``: 15-bit label plus
    the valid flag in bit 15) unpacked inside the kernel and ANDed with
    ``valid``.
    rev_lut: int32[2^15] shared across the batch, or int32[batch, 2^15] with
    one reverse LUT per stream (stacked hierarchical routing).
    Returns (out_labels i32[batch, capacity], out_valid i32[batch, capacity],
             dropped i32[batch, 1]).

    Timed datapath: with ``times`` (int32[batch, n_events] timestamp lane)
    and ``queue`` (static (service_ns, cc_interval, stall_total_ns) from
    ``latency.TimedWire.queue``) the lane rides the pack and accumulates the
    destination's rank-dependent queueing in-kernel; the return gains
    ``out_times i32[batch, capacity]`` before ``dropped``.
    """
    if (times is None) != (queue is None):
        raise ValueError("the timed merge needs both the timestamp lane and "
                         "the static queue constants (times XOR queue given)")
    packed = _pack_call(valid, labels, capacity=capacity, interpret=interpret,
                        times=times, queue=queue,
                        wire16=labels.dtype == jnp.int16)
    out_l, out_v = _rev_lookup(rev_lut, packed[0], packed[-2])
    dropped = packed[-1][:, None]
    if queue is None:
        return out_l, out_v, dropped
    return out_l, out_v, jnp.where(out_v == 1, packed[1], 0), dropped
