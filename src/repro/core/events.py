"""Event-frame representation of sparse spike traffic.

The BSS-2 layer-2 protocol packs up to three spike events (16-bit labels +
8-bit timestamps) into one link word for bandwidth efficiency; the multi-chip
extension unpacks them to single events in the 250 MHz MGT clock domain.

JAX requires static shapes, so sparse event streams are carried as
fixed-capacity ``EventFrame``s: a dense buffer of labels/timestamps plus a
validity mask.  Capacity overflow drops events and counts them — the same
semantics as the paper's lossy layer-1 path under continued congestion.

Compaction scheme: ``make_frame`` ranks the valid events with a prefix sum
over the validity mask — the hardware's pack unit — rather than a stable
sort, and fills each output slot with the event of its rank.  Arrival order
and drop counts are identical to the retired argsort scheme; the only
observable difference is that invalid slots are zero-filled instead of
carrying sorted garbage.  This jnp form is the path off the TPU, and the
fabric's uplink packs take it everywhere.  On the TPU the chips' egress
(``repro.snn.stream``) packs through the Pallas pack kernel instead
(``repro.kernels.spike_router.ops.pack_frame``), with the same result.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LABEL_DTYPE = jnp.int32
TIME_DTYPE = jnp.int32

# Layer-2 packing factor: up to three spikes per link word (paper §III).
SPIKES_PER_WORD = 3
# Layer-2 timestamps carry the lower eight bits of the system time.
TIMESTAMP_BITS = 8
TIMESTAMP_MASK = (1 << TIMESTAMP_BITS) - 1


class EventFrame(NamedTuple):
    """A fixed-capacity batch of spike events.

    Attributes:
      labels: int32[..., capacity] spike labels (16-bit payload range).
      times:  int32[..., capacity] event timestamps (system-clock cycles).
      valid:  bool[..., capacity]  validity mask; invalid slots are padding.
    """

    labels: jax.Array
    times: jax.Array
    valid: jax.Array

    @property
    def capacity(self) -> int:
        return self.labels.shape[-1]

    def count(self) -> jax.Array:
        return jnp.sum(self.valid, axis=-1)


def empty_frame(capacity: int, batch_shape: tuple[int, ...] = ()) -> EventFrame:
    shape = (*batch_shape, capacity)
    return EventFrame(
        labels=jnp.zeros(shape, LABEL_DTYPE),
        times=jnp.zeros(shape, TIME_DTYPE),
        valid=jnp.zeros(shape, jnp.bool_),
    )


def _rank_gather_pack(labels2, times2, csum, capacity: int):
    """Shared gather-form pack tail: slot j holds the event of rank j+1,
    located by a vectorized binary search on the monotone inclusive prefix
    sum ``csum`` [b, n].  Returns (out_l, out_t, out_v, total, kept)."""
    b, n = labels2.shape
    total = csum[:, -1]
    kept = jnp.minimum(total, capacity)
    ranks = jnp.arange(1, capacity + 1, dtype=csum.dtype)
    src = jax.vmap(lambda c: jnp.searchsorted(c, ranks, side="left"))(csum)
    src = jnp.minimum(src, n - 1)                    # clamp empty-slot probes
    out_v = jnp.arange(capacity, dtype=kept.dtype)[None] < kept[:, None]
    out_l = jnp.where(out_v, jnp.take_along_axis(labels2, src, axis=-1), 0)
    if times2 is None:
        out_t = jnp.zeros((b, capacity), TIME_DTYPE)
    else:
        out_t = jnp.where(out_v, jnp.take_along_axis(times2, src, axis=-1), 0)
    return out_l, out_t, out_v, total, kept


def make_frame(labels, times, valid, capacity: int) -> tuple[EventFrame, jax.Array]:
    """Compact events to the front of a capacity-bounded frame.

    This is the hardware pack unit: an inclusive prefix sum over the validity
    mask ranks each valid event (arrival order preserved), and every output
    slot j gathers the event with rank j+1 via a vectorized binary search on
    the monotone prefix sums.  O(C log N) gathers instead of the O(N log N)
    stable sort plus three payload permutations of ``make_frame_argsort``.
    On the TPU these per-element gathers are slow; the stream's egress packs
    there through ``repro.kernels.spike_router.ops.pack_frame``, whose
    kernel compares each event's rank with every output slot and sums the
    one writer's value in VMEM, and whose ``"jax"`` path is this function.
    Events ranked beyond ``capacity`` are dropped and counted (layer-1
    congestion semantics).  Invalid output slots are zero-filled — labels
    and times of padding are always 0.

    ``times=None`` skips the timestamp gather and emits zeros (the exchange
    paths discard timestamps at egress, §III).

    Returns (frame, dropped_count).
    """
    labels = jnp.asarray(labels, LABEL_DTYPE)
    valid = jnp.asarray(valid, jnp.bool_)

    lead = labels.shape[:-1]
    n = labels.shape[-1]
    labels2 = labels.reshape(-1, n)
    valid2 = valid.reshape(-1, n)
    b = labels2.shape[0]

    if n == 0:
        frame = empty_frame(capacity, lead)
        return frame, jnp.zeros(lead, jnp.int32)

    ok = valid2.astype(jnp.int32)
    csum = jnp.cumsum(ok, axis=-1)                   # inclusive prefix sum
    times2 = (None if times is None
              else jnp.asarray(times, TIME_DTYPE).reshape(-1, n))
    out_l, out_t, out_v, total, kept = _rank_gather_pack(labels2, times2,
                                                         csum, capacity)

    frame = EventFrame(
        labels=out_l.reshape(*lead, capacity).astype(LABEL_DTYPE),
        times=out_t.reshape(*lead, capacity).astype(TIME_DTYPE),
        valid=out_v.reshape(*lead, capacity),
    )
    dropped = (total - kept).astype(jnp.int32).reshape(lead)
    return frame, dropped


def _segment_groups(seg_lens: tuple[int, ...]):
    """Contiguous runs of equal segment length: [(first, last+1, length)]."""
    groups = []
    i = 0
    while i < len(seg_lens):
        j = i
        while j < len(seg_lens) and seg_lens[j] == seg_lens[i]:
            j += 1
        groups.append((i, j, seg_lens[i]))
        i = j
    return groups


def make_frame_segmented(labels, times, valid, capacity: int,
                         seg_lens: tuple[int, ...], *,
                         compact: bool = False) -> tuple[EventFrame, jax.Array]:
    """Two-level (segmented) pack unit — bit-exact with ``make_frame``.

    The trailing axis is treated as contiguous segments of ``seg_lens`` slots
    (static; they must sum to ``labels.shape[-1]``).  Packing runs in two
    levels: per-segment valid counts, a small exclusive scan over the segment
    totals for base offsets, then per-segment placement — the per-destination
    work is tiled over source blocks instead of one O(N) prefix-sum chain.
    Because segments are contiguous, ``base[seg] + within-segment rank`` *is*
    the global arrival rank, so order and drop counts are identical to the
    global pack.

    ``compact=True`` promises every segment's valid events are already
    front-compacted (each segment is itself the output of a pack, as
    guaranteed by the compact-before-gather exchange paths, and validity is
    only ever gated per whole segment downstream).  The pack then gathers
    output slot i straight from segment offsets located by a binary search
    over the S segment totals — O(capacity·log S) index work, never touching
    the N-slot stream beyond the count reduction.  Results are undefined if
    the promise is broken.

    Returns (frame, dropped_count) like ``make_frame``.
    """
    seg_lens = tuple(int(s) for s in seg_lens)
    labels = jnp.asarray(labels, LABEL_DTYPE)
    valid = jnp.asarray(valid, jnp.bool_)
    lead = labels.shape[:-1]
    n = labels.shape[-1]
    if not seg_lens or min(seg_lens) <= 0 or sum(seg_lens) != n:
        raise ValueError(f"seg_lens {seg_lens} must be positive and sum to "
                         f"the stream length {n}")
    n_seg = len(seg_lens)
    starts = np.concatenate(([0], np.cumsum(seg_lens)))[:-1]
    groups = _segment_groups(seg_lens)

    labels2 = labels.reshape(-1, n)
    valid2 = valid.reshape(-1, n)
    times2 = (None if times is None
              else jnp.asarray(times, TIME_DTYPE).reshape(-1, n))
    b = labels2.shape[0]
    ok = valid2.astype(jnp.int32)

    # Level 1: per-segment counts (a reduction, not a scan).
    counts = jnp.concatenate(
        [ok[:, starts[i]:starts[i] + (j - i) * sl].reshape(b, j - i, sl)
         .sum(axis=-1) for i, j, sl in groups], axis=-1)       # [b, n_seg]
    # Level 2: exclusive scan over the S segment totals (S is small).
    cum = jnp.cumsum(counts, axis=-1)
    base = cum - counts
    total = cum[:, -1]
    kept = jnp.minimum(total, capacity)
    dropped = (total - kept).astype(jnp.int32).reshape(lead)

    if compact:
        # Bounded per-segment gather: slot i lives in the segment whose
        # cumulative count first exceeds i, at offset i - base[seg].
        slots = jnp.arange(capacity, dtype=cum.dtype)
        seg_of = jax.vmap(
            lambda c: jnp.searchsorted(c, slots, side="right"))(cum)
        seg_of = jnp.minimum(seg_of, n_seg - 1)
        out_v = slots[None, :] < kept[:, None]
        offset = slots[None, :] - jnp.take_along_axis(base, seg_of, axis=-1)
        src = jnp.asarray(starts, jnp.int32)[seg_of] + offset
        src = jnp.where(out_v, src, 0)
        out_l = jnp.where(out_v, jnp.take_along_axis(labels2, src, axis=-1), 0)
        if times2 is None:
            out_t = jnp.zeros((b, capacity), TIME_DTYPE)
        else:
            out_t = jnp.where(out_v,
                              jnp.take_along_axis(times2, src, axis=-1), 0)
    else:
        # General segments: within-segment inclusive scans + base offsets
        # reassemble the global inclusive prefix sum without one length-N
        # dependency chain; the tail is the shared rank gather.
        csum = jnp.concatenate(
            [(jnp.cumsum(ok[:, starts[i]:starts[i] + (j - i) * sl]
                         .reshape(b, j - i, sl), axis=-1)
              + base[:, i:j, None]).reshape(b, (j - i) * sl)
             for i, j, sl in groups], axis=-1)                 # [b, n]
        out_l, out_t, out_v, _, _ = _rank_gather_pack(labels2, times2, csum,
                                                      capacity)

    frame = EventFrame(
        labels=out_l.reshape(*lead, capacity).astype(LABEL_DTYPE),
        times=out_t.reshape(*lead, capacity).astype(TIME_DTYPE),
        valid=out_v.reshape(*lead, capacity),
    )
    return frame, dropped


def make_frame_argsort(labels, times, valid,
                       capacity: int) -> tuple[EventFrame, jax.Array]:
    """The seed's stable-argsort compaction, kept as the benchmark baseline.

    Semantically equivalent to ``make_frame`` for (labels·valid, times·valid,
    valid, dropped); invalid slots carry sorted garbage rather than zeros.
    """
    labels = jnp.asarray(labels, LABEL_DTYPE)
    times = jnp.asarray(times, TIME_DTYPE)
    valid = jnp.asarray(valid, jnp.bool_)
    # Stable order: valid events first, preserving arrival order.
    order = jnp.argsort(~valid, axis=-1, stable=True)
    labels = jnp.take_along_axis(labels, order, axis=-1)
    times = jnp.take_along_axis(times, order, axis=-1)
    valid = jnp.take_along_axis(valid, order, axis=-1)

    n = labels.shape[-1]
    total = jnp.sum(valid, axis=-1)
    if n >= capacity:
        frame = EventFrame(
            labels=labels[..., :capacity],
            times=times[..., :capacity],
            valid=valid[..., :capacity],
        )
        dropped = total - jnp.sum(frame.valid, axis=-1)
    else:
        pad = capacity - n
        pad_widths = [(0, 0)] * (labels.ndim - 1) + [(0, pad)]
        frame = EventFrame(
            labels=jnp.pad(labels, pad_widths),
            times=jnp.pad(times, pad_widths),
            valid=jnp.pad(valid, pad_widths),
        )
        dropped = jnp.zeros_like(total)
    return frame, dropped


def concatenate_frames(frames: list[EventFrame], capacity: int) -> tuple[EventFrame, jax.Array]:
    """Merge several frames into one capacity-bounded frame (drops overflow)."""
    labels = jnp.concatenate([f.labels for f in frames], axis=-1)
    times = jnp.concatenate([f.times for f in frames], axis=-1)
    valid = jnp.concatenate([f.valid for f in frames], axis=-1)
    return make_frame(labels, times, valid, capacity)


# ---------------------------------------------------------------------------
# 16-bit wire format (one int16 word per on-wire event slot)
# ---------------------------------------------------------------------------

# On the MGT lane an event is one 16-bit word: 15 label bits (one MGT bit is
# reserved for command messages, mirrored by ``routing.WIRE_LABEL_BITS``) —
# the software wire format reuses that spare bit as the slot-validity flag,
# so gathered exchange streams travel as int16 instead of int32 labels plus
# a separate mask, halving gather bandwidth.
WIRE_WORD_DTYPE = jnp.int16
WIRE_VALID_BIT = 15
WIRE_PAYLOAD_MASK = (1 << WIRE_VALID_BIT) - 1


def pack_wire16(labels, valid) -> jax.Array:
    """Encode (15-bit wire labels, validity) into int16 wire words.

    Invalid slots encode as word 0 regardless of their label payload, so
    packed frames keep their zero-filled padding on the wire.
    """
    labels = jnp.asarray(labels, jnp.int32) & WIRE_PAYLOAD_MASK
    valid = jnp.asarray(valid).astype(jnp.int32)
    word = jnp.where(valid == 1, labels | (1 << WIRE_VALID_BIT), 0)
    return word.astype(WIRE_WORD_DTYPE)


def unpack_wire16(words) -> tuple[jax.Array, jax.Array]:
    """Decode int16 wire words into (int32 15-bit labels, bool validity)."""
    w = jnp.asarray(words).astype(jnp.int32) & 0xFFFF
    return w & WIRE_PAYLOAD_MASK, (w >> WIRE_VALID_BIT) == 1


# ---------------------------------------------------------------------------
# Layer-2 word packing (≤3 spikes per word + shared 8-bit timestamp tag)
# ---------------------------------------------------------------------------


class PackedWords(NamedTuple):
    """Layer-2 packed representation: groups of up to three events per word."""

    labels: jax.Array  # int32[..., n_words, SPIKES_PER_WORD]
    times: jax.Array   # int32[..., n_words]  (lower 8 bits of system time)
    valid: jax.Array   # bool[..., n_words, SPIKES_PER_WORD]


def pack_words(frame: EventFrame) -> PackedWords:
    """Pack an event frame into layer-2 words (3 spikes/word).

    The word timestamp is the tag of its first *valid* slot (the hardware
    packs temporally adjacent events; frames are already time-ordered here);
    a word with no valid slot carries tag 0.
    """
    cap = frame.capacity
    n_words = -(-cap // SPIKES_PER_WORD)
    pad = n_words * SPIKES_PER_WORD - cap
    pad_widths = [(0, 0)] * (frame.labels.ndim - 1) + [(0, pad)]
    labels = jnp.pad(frame.labels, pad_widths)
    times = jnp.pad(frame.times, pad_widths)
    valid = jnp.pad(frame.valid, pad_widths)

    new_shape = (*frame.labels.shape[:-1], n_words, SPIKES_PER_WORD)
    labels = labels.reshape(new_shape)
    times = times.reshape(new_shape)
    valid = valid.reshape(new_shape)
    first_valid = jnp.argmax(valid, axis=-1)
    first_time = jnp.take_along_axis(times, first_valid[..., None],
                                     axis=-1)[..., 0]
    word_time = jnp.where(jnp.any(valid, axis=-1),
                          jnp.bitwise_and(first_time, TIMESTAMP_MASK), 0)
    return PackedWords(labels=labels, times=word_time, valid=valid)


def unpack_words(words: PackedWords, base_time: jax.Array | int = 0,
                 capacity: int | None = None) -> EventFrame:
    """Unpack layer-2 words back into single events.

    ``base_time`` supplies the upper timestamp bits (the receiving FPGA's
    synchronized system time); the multi-chip extension itself *discards* the
    timestamp, which callers model by passing 0 and ignoring ``times``.

    ``capacity`` restores the original frame capacity: ``pack_words`` pads
    the frame up to a whole number of 3-spike words, and without this
    argument the padding slots (always invalid) stay in the frame, silently
    growing it from ``capacity`` to ``ceil(capacity/3)*3``.  Pass the
    capacity of the frame that was packed to round-trip exactly; ``None``
    keeps every slot (the word-aligned view).
    """
    lead = words.labels.shape[:-2]
    cap = words.labels.shape[-2] * SPIKES_PER_WORD
    labels = words.labels.reshape(*lead, cap)
    valid = words.valid.reshape(*lead, cap)
    base = jnp.asarray(base_time, TIME_DTYPE)
    upper = jnp.bitwise_and(base, ~jnp.int32(TIMESTAMP_MASK))
    times = upper + words.times[..., None]
    times = jnp.broadcast_to(times, words.labels.shape).reshape(*lead, cap)
    if capacity is not None:
        if not cap - SPIKES_PER_WORD < capacity <= cap:
            raise ValueError(
                f"capacity {capacity} does not match {words.labels.shape[-2]} "
                f"packed words ({cap} slots)")
        labels = labels[..., :capacity]
        times = times[..., :capacity]
        valid = valid[..., :capacity]
    return EventFrame(labels=labels, times=times, valid=valid)


def words_required(n_events: jax.Array) -> jax.Array:
    """Number of layer-2 words needed for ``n_events`` spikes (ceil div 3)."""
    return -(-n_events // SPIKES_PER_WORD)


@dataclasses.dataclass(frozen=True)
class CapacityPolicy:
    """How event-frame capacity is provisioned.

    ``strict`` mirrors hardware (fixed capacity, silent drop + counter);
    ``provisioned`` sizes capacity from an expected-rate bound so gradient
    based training sees loss-free traffic (see DESIGN.md §2).
    """

    mode: str = "strict"  # "strict" | "provisioned"
    headroom: float = 2.0

    def capacity_for(self, expected_events: int) -> int:
        if self.mode == "provisioned":
            return max(8, int(expected_events * self.headroom))
        return max(8, int(expected_events))
