"""Public jit'd wrappers for the fused exchange datapath.

``pack_frame``             the bare capacity pack, no LUT: the kernel twin
                           of ``events.make_frame`` with the same return.
                           The chips' egress in ``repro.snn.stream`` packs
                           through it.
``route_and_pack``         egress only: fwd LUT + enable mask + capacity
                           pack (``interpret=True`` off the TPU).
``fused_exchange``         the full round (fwd LUT → route enables → merge →
                           pack → rev LUT), batched over destinations — what
                           ``repro.core.aggregator.route_step`` runs.
``fused_exchange_stream``  T full rounds in one program: the round's kernel
                           over a leading timestep grid axis on TPU, a
                           ``lax.scan`` over the fused round elsewhere —
                           what ``benchmarks/exchange_stream.py`` runs.
``fused_merge_pack``       merge + pack + rev LUT for streams whose fwd LUT
                           ran on the sender (the ``shard_map`` exchange
                           path); accepts a shared or per-stream rev LUT.

Mode selection is automatic (``mode=None``): the compiled Pallas kernel on
TPU, the pure-jnp oracle elsewhere; ``mode="interpret"`` forces the Pallas
interpreter for parity testing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.events import EventFrame, make_frame
from repro.kernels import MODE_INTERPRET, MODE_JAX, MODE_PALLAS, default_mode
from repro.kernels.spike_router import ref as _ref
from repro.kernels.spike_router.spike_router import (_pack_call,
                                                     exchange_fwd,
                                                     exchange_stream_fwd,
                                                     merge_pack_fwd,
                                                     spike_router_fwd)


@functools.partial(jax.jit, static_argnames=("capacity", "mode"))
def pack_frame(labels: jax.Array, valid: jax.Array, *, capacity: int,
               mode: str | None = None) -> tuple[EventFrame, jax.Array]:
    """Compact events to the front of a capacity-bounded frame.

    labels, valid: [..., n_events].  The same result as
    ``events.make_frame(labels, None, valid, capacity)``, which is the
    ``"jax"`` path: arrival order kept, events ranked beyond ``capacity``
    dropped and counted, invalid slots zero-filled, ``times`` all zeros.
    The kernel ranks by a one-hot compare and select-and-sum in VMEM, with
    no per-element gather.

    Returns (frame, dropped i32[...]).
    """
    if mode is None:
        mode = default_mode()
    if mode == MODE_JAX:
        return make_frame(labels, None, valid, capacity)
    if mode not in (MODE_PALLAS, MODE_INTERPRET):
        raise ValueError(f"unknown exchange mode: {mode!r}")
    lead = labels.shape[:-1]
    n = labels.shape[-1]
    out_l, out_v, dropped = _pack_call(
        valid.reshape(-1, n).astype(jnp.int32),
        labels.reshape(-1, n).astype(jnp.int32), capacity=capacity,
        interpret=mode == MODE_INTERPRET)
    out_l = out_l.reshape(*lead, capacity)
    frame = EventFrame(labels=out_l, times=jnp.zeros_like(out_l),
                       valid=out_v.reshape(*lead, capacity).astype(jnp.bool_))
    return frame, dropped.reshape(lead)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def route_and_pack(labels: jax.Array, valid: jax.Array, lut: jax.Array, *,
                   capacity: int, interpret: bool = False):
    """Fused LUT-route + enable-mask + capacity-pack.

    labels: int[..., n_events]; valid: bool/int[..., n_events];
    lut: int32[65536] forward routing table.

    Returns (out_labels i32[..., capacity], out_valid bool[..., capacity],
             dropped i32[...]).
    """
    lead = labels.shape[:-1]
    n = labels.shape[-1]
    labels2 = labels.reshape(-1, n).astype(jnp.int32)
    valid2 = valid.reshape(-1, n).astype(jnp.int32)
    out_l, out_v, dropped = spike_router_fwd(
        labels2, valid2, lut.astype(jnp.int32), capacity=capacity,
        interpret=interpret)
    return (out_l.reshape(*lead, capacity),
            out_v.reshape(*lead, capacity).astype(jnp.bool_),
            dropped.reshape(*lead))


@functools.partial(jax.jit, static_argnames=("capacity", "mode"))
def fused_exchange(labels: jax.Array, valid: jax.Array, fwd_luts: jax.Array,
                   rev_luts: jax.Array, enables: jax.Array, *,
                   capacity: int, mode: str | None = None):
    """One full exchange round for all destinations.

    labels, valid: [n_src, cap_in] per-source egress frames (shared — never
    copied per destination); fwd_luts: int32[n_src, 2^16];
    rev_luts: int32[n_dst, 2^15]; enables: bool/int[n_src, n_dst].

    Returns (out_labels i32[n_dst, capacity], out_valid bool[n_dst, capacity],
             dropped i32[n_dst]).
    """
    if mode is None:
        mode = default_mode()
    labels = labels.astype(jnp.int32)
    if mode == MODE_JAX:
        out_l, out_v, dropped = _ref.exchange_ref(
            labels, valid, fwd_luts, rev_luts, enables, capacity=capacity)
    elif mode in (MODE_PALLAS, MODE_INTERPRET):
        out_l, out_v, dropped = exchange_fwd(
            labels, valid.astype(jnp.int32), fwd_luts.astype(jnp.int32),
            rev_luts.astype(jnp.int32), enables.astype(jnp.int32),
            capacity=capacity, interpret=mode == MODE_INTERPRET)
        dropped = dropped[:, 0]
    else:
        raise ValueError(f"unknown exchange mode: {mode!r}")
    return out_l, out_v.astype(jnp.bool_), dropped


@functools.partial(jax.jit, static_argnames=("capacity", "mode"))
def fused_exchange_stream(labels: jax.Array, valid: jax.Array,
                          fwd_luts: jax.Array, rev_luts: jax.Array,
                          enables: jax.Array, *, capacity: int,
                          mode: str | None = None):
    """T full exchange rounds as one compiled program.

    labels, valid: [n_steps, n_src, cap_in] per-timestep egress frames;
    fwd_luts: int32[n_src, 2^16]; rev_luts: int32[n_dst, 2^15];
    enables: bool/int[n_src, n_dst] (static over the stream — routing tables
    are configuration, not data, §III).

    Returns (out_labels i32[n_steps, n_dst, capacity],
             out_valid bool[n_steps, n_dst, capacity],
             dropped i32[n_steps, n_dst]).
    """
    if mode is None:
        mode = default_mode()
    labels = labels.astype(jnp.int32)
    if mode == MODE_JAX:
        out_l, out_v, dropped = _ref.exchange_stream_ref(
            labels, valid, fwd_luts, rev_luts, enables, capacity=capacity)
    elif mode in (MODE_PALLAS, MODE_INTERPRET):
        out_l, out_v, dropped = exchange_stream_fwd(
            labels, valid.astype(jnp.int32), fwd_luts.astype(jnp.int32),
            rev_luts.astype(jnp.int32), enables.astype(jnp.int32),
            capacity=capacity, interpret=mode == MODE_INTERPRET)
    else:
        raise ValueError(f"unknown exchange mode: {mode!r}")
    return out_l, out_v.astype(jnp.bool_), dropped


@functools.partial(jax.jit, static_argnames=("capacity", "mode", "seg_lens",
                                             "compact", "queue"))
def fused_merge_pack(labels: jax.Array, valid: jax.Array, rev_lut: jax.Array,
                     *, capacity: int, mode: str | None = None,
                     seg_lens: tuple[int, ...] | None = None,
                     compact: bool = False, times: jax.Array | None = None,
                     queue: tuple[int, int, int] | None = None):
    """Merge + pack + rev LUT for pre-routed wire-label streams.

    labels, valid: [..., n_events] (fwd LUT + route enables already applied);
    ``labels`` is int32 wire labels or int16 wire words
    (``events.pack_wire16``) whose embedded valid bit is unpacked inside the
    merge and ANDed with ``valid``.  ``valid`` must match ``labels``
    slot-for-slot — implicit broadcasting is rejected.
    rev_lut: int32[2^15] shared across the batch, or int32[batch, 2^15] with
    one LUT per stream (the leading label dims must flatten to ``batch``).
    seg_lens: static per-source-segment slot counts along the event axis —
    the pack runs as the two-level segmented unit tiled over source blocks.
    compact: promise that every segment's valid events are front-compacted
    (compact-before-gather streams), enabling the bounded per-segment gather
    on the oracle path.

    Returns (out_labels i32[..., capacity], out_valid bool[..., capacity],
             dropped i32[...]).

    Timed datapath: ``times`` is the int32[..., n_events] timestamp lane
    (departure + accumulated fixed/uplink delay so far) and ``queue`` the
    static (service_ns, cc_interval, stall_total_ns) triple from
    ``latency.TimedWire.queue``.  The lane rides the pack's scatter and
    picks up the destination's rank-dependent queueing inside the kernel
    (oracle and Pallas paths bit-exact); the return gains
    ``out_times i32[..., capacity]`` before ``dropped``.
    """
    if mode is None:
        mode = default_mode()
    if valid.shape != labels.shape:
        raise ValueError(
            f"valid shape {valid.shape} must match labels shape "
            f"{labels.shape} slot-for-slot; implicit broadcasting would "
            "mis-rank the merge stream in the pack unit")
    if (times is None) != (queue is None):
        raise ValueError("the timed merge needs both the timestamp lane and "
                         "the static queue constants (times XOR queue given)")
    if times is not None and times.shape != labels.shape:
        raise ValueError(
            f"times shape {times.shape} must match labels shape "
            f"{labels.shape} slot-for-slot (the lane rides the same pack)")
    if seg_lens is not None:
        seg_lens = tuple(int(s) for s in seg_lens)
        if sum(seg_lens) != labels.shape[-1]:
            raise ValueError(f"seg_lens {seg_lens} must sum to the stream "
                             f"length {labels.shape[-1]}")
    if labels.dtype != jnp.int16:      # int16 = wire words, decoded in-kernel
        labels = labels.astype(jnp.int32)
    if rev_lut.ndim == 2:
        n_streams = 1
        for d in labels.shape[:-1]:
            n_streams *= d
        if n_streams != rev_lut.shape[0]:
            raise ValueError(
                f"per-stream rev LUTs: {rev_lut.shape[0]} tables do not "
                f"match {n_streams} streams (labels {labels.shape})")
    if mode == MODE_JAX:
        outs = _ref.merge_pack_ref(
            labels, valid, rev_lut, capacity=capacity, seg_lens=seg_lens,
            compact=compact, times=times, queue=queue)
    elif mode in (MODE_PALLAS, MODE_INTERPRET):
        # The kernel tiles the rank over fixed 128-event tiles; ``seg_lens``
        # only steers the oracle (tiling is a scheduling choice, not a
        # semantic one).
        lead = labels.shape[:-1]
        n = labels.shape[-1]
        outs = merge_pack_fwd(
            labels.reshape(-1, n), valid.reshape(-1, n).astype(jnp.int32),
            rev_lut.astype(jnp.int32), capacity=capacity,
            interpret=mode == MODE_INTERPRET,
            times=None if times is None
            else times.reshape(-1, n).astype(jnp.int32),
            queue=queue)
        outs = (*(o.reshape(*lead, capacity) for o in outs[:-1]),
                outs[-1].reshape(lead))
    else:
        raise ValueError(f"unknown exchange mode: {mode!r}")
    if queue is None:
        out_l, out_v, dropped = outs
        return out_l, out_v.astype(jnp.bool_), dropped
    out_l, out_v, out_t, dropped = outs
    return out_l, out_v.astype(jnp.bool_), out_t, dropped
