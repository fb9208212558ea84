"""Pure-jnp oracles for the fused exchange datapath.

Built directly on ``repro.core`` (the semantic implementation) so the kernels
are validated against the same code the SNN substrate runs.  Because
``repro.core.events.make_frame`` is itself the cumsum/scatter pack unit,
these oracles are also the *fast compiled path* on non-TPU backends — the
ops layer dispatches here when Pallas would only be interpreted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.events import make_frame, make_frame_segmented, unpack_wire16
from repro.core.routing import lookup_fwd, lookup_rev
from repro.kernels.spike_router.spike_router import _dest_queue_ns


def spike_router_ref(labels, valid, lut, *, capacity: int):
    """Egress-only oracle: (out_labels, out_valid, dropped) per frame."""
    labels = jnp.asarray(labels, jnp.int32)
    valid = jnp.asarray(valid).astype(jnp.bool_)
    wire, enabled = lookup_fwd(lut, labels)
    frame, dropped = make_frame(wire, jnp.zeros_like(wire), valid & enabled,
                                capacity)
    out_labels = jnp.where(frame.valid, frame.labels, 0)
    return (out_labels.astype(jnp.int32),
            frame.valid.astype(jnp.int32),
            dropped.astype(jnp.int32)[..., None])


def exchange_ref(labels, valid, fwd_luts, rev_luts, enables, *,
                 capacity: int):
    """Full-round oracle matching ``exchange_fwd``.

    labels, valid: [n_src, cap_in]; fwd_luts: [n_src, 2^16];
    rev_luts: [n_dst, 2^15]; enables: [n_src, n_dst].
    Returns (out_labels i32[n_dst, capacity], out_valid i32[n_dst, capacity],
             dropped i32[n_dst]).
    """
    labels = jnp.asarray(labels, jnp.int32)
    valid = jnp.asarray(valid).astype(jnp.bool_)
    enables = jnp.asarray(enables).astype(jnp.bool_)
    n_src, cap_in = labels.shape
    n_dst = enables.shape[1]
    n = n_src * cap_in

    wire, fwd_en = jax.vmap(lookup_fwd)(fwd_luts, labels)
    # Shared src-major stream; per-destination validity mask only.  The
    # segmented pack tiles the merge over the n_src source blocks.
    flat_wire = wire.reshape(n)
    ok = (valid & fwd_en)[:, None, :] & enables[:, :, None]
    ok = jnp.swapaxes(ok, 0, 1).reshape(n_dst, n)
    frame, dropped = make_frame_segmented(
        jnp.broadcast_to(flat_wire[None], (n_dst, n)), None, ok, capacity,
        (cap_in,) * n_src)
    chip, rev_en = jax.vmap(lookup_rev)(rev_luts, frame.labels)
    out_valid = frame.valid & rev_en
    out_labels = jnp.where(out_valid, chip, 0)
    return (out_labels.astype(jnp.int32), out_valid.astype(jnp.int32),
            dropped.astype(jnp.int32))


def exchange_stream_ref(labels, valid, fwd_luts, rev_luts, enables, *,
                        capacity: int):
    """Multi-step oracle matching ``exchange_stream_fwd``: one
    ``lax.scan`` over ``exchange_ref`` — a single compiled program with the
    LUTs hoisted to loop invariants, not T dispatches.

    labels, valid: [T, n_src, cap_in].
    Returns (out_labels i32[T, n_dst, capacity],
             out_valid i32[T, n_dst, capacity], dropped i32[T, n_dst]).
    """
    labels = jnp.asarray(labels, jnp.int32)
    valid = jnp.asarray(valid).astype(jnp.bool_)

    def body(_, frame):
        lab, val = frame
        return None, exchange_ref(lab, val, fwd_luts, rev_luts, enables,
                                  capacity=capacity)

    _, outs = jax.lax.scan(body, None, (labels, valid))
    return outs


def merge_pack_ref(labels, valid, rev_lut, *, capacity: int,
                   seg_lens: tuple[int, ...] | None = None,
                   compact: bool = False, times=None,
                   queue: tuple[int, int, int] | None = None):
    """Merge-pack-rev oracle matching ``merge_pack_fwd``.

    labels, valid: [..., n_events] pre-routed wire labels; ``labels`` may be
    int16 wire words (``events.pack_wire16``) — the embedded valid bit is
    unpacked here and ANDed with ``valid``.  ``seg_lens`` switches the pack
    to the two-level segmented unit (static per-segment slot counts);
    ``compact`` additionally promises front-compacted segments, enabling the
    bounded per-segment gather.
    rev_lut: [2^15] shared, or [batch, 2^15] per-stream (the leading label
    dims must then flatten to ``batch``).
    Returns (out_labels i32[..., capacity], out_valid i32[..., capacity],
             dropped i32[...]).

    Timed datapath: ``times`` (int32[..., n_events]) rides the pack and, as
    in the kernel, picks up the destination queueing of its pack rank
    (``queue`` = static (service_ns, cc_interval, stall_total_ns)); the
    return gains ``out_times`` before ``dropped``.
    """
    valid = jnp.asarray(valid).astype(jnp.bool_)
    if jnp.asarray(labels).dtype == jnp.int16:
        labels, word_valid = unpack_wire16(labels)
        valid = valid & word_valid
    labels = jnp.asarray(labels, jnp.int32)
    if (times is None) != (queue is None):
        raise ValueError("the timed merge needs both the timestamp lane and "
                         "the static queue constants (times XOR queue given)")
    if seg_lens is None:
        frame, dropped = make_frame(labels, times, valid, capacity)
    else:
        frame, dropped = make_frame_segmented(labels, times, valid, capacity,
                                              seg_lens, compact=compact)
    if rev_lut.ndim == 2:
        lead = frame.labels.shape[:-1]
        flat = frame.labels.reshape(rev_lut.shape[0], capacity)
        chip, rev_en = jax.vmap(lookup_rev)(rev_lut, flat)
        chip = chip.reshape(*lead, capacity)
        rev_en = rev_en.reshape(*lead, capacity)
    else:
        chip, rev_en = lookup_rev(rev_lut, frame.labels)
    out_valid = frame.valid & rev_en
    out_labels = jnp.where(out_valid, chip, 0)
    if queue is None:
        return (out_labels.astype(jnp.int32), out_valid.astype(jnp.int32),
                dropped.astype(jnp.int32))
    arrive = (frame.times.astype(jnp.int32)
              + _dest_queue_ns(capacity, queue)[0])
    out_times = jnp.where(out_valid, arrive, 0)
    return (out_labels.astype(jnp.int32), out_valid.astype(jnp.int32),
            out_times.astype(jnp.int32), dropped.astype(jnp.int32))
