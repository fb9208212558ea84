"""Pallas-vs-oracle conformance matrix (ISSUE 4 satellite).

One parameterized battery replaces the parity checks scattered across the
stream/sparse test modules: every public fused-exchange op is driven through
the pure-jnp oracle (``mode="jax"``) and the Pallas interpreter
(``mode="interpret"``) over the full configuration matrix —

    op         ∈ {exchange_fwd, merge_pack_fwd, exchange_stream_fwd}
    occupancy  ∈ {0 %, 2 %, 50 %, 100 %}
    stream     ∈ {one tile, three}    (merge_pack only — the kernel's
                                       128-event tiles)
    wire16     ∈ {off, on}            (merge_pack only)
    pack       ∈ {global, segmented}  (merge_pack only)
    timed      ∈ {off, on}            (merge_pack only — the timestamp lane)

— and must agree bit-for-bit on every observable: labels, validity,
timestamps, and drop counts.  Arrival order is additionally pinned against a
straight numpy replay of the merge semantics, so both modes are checked
against the specification, not only against each other.

The ``exchange_mode="routed"`` battery (ISSUE 9) extends the matrix to the
stacked hop-graph executor's wire strategies: routed (static edge-schedule
merge) vs gather (broadcast plane) over occupancy × uplink caps × timed ×
degraded detours, bit-exact on every observable including all four
``ExchangeDrops`` fields.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import identity_router, pack_wire16, timed_wire
from repro.core.routing import WIRE_LABEL_MASK
from repro.kernels.spike_router.ops import (fused_exchange,
                                            fused_exchange_stream,
                                            fused_merge_pack)

KEY = jax.random.key(31)
OCCUPANCIES = (0.0, 0.02, 0.5, 1.0)
N_SRC, CAP_IN, CAPACITY = 3, 24, 16          # CAPACITY < traffic ⇒ drops
TIMING = timed_wire()


def _frames(key, shape, occupancy):
    labels = jax.random.randint(key, shape, 0, 2 ** 15)
    valid = jax.random.uniform(jax.random.fold_in(key, 1), shape) < occupancy
    return labels, valid


def _assert_all_equal(outs_jax, outs_interpret):
    assert len(outs_jax) == len(outs_interpret)
    for a, b in zip(outs_jax, outs_interpret):
        assert a.dtype == b.dtype and jnp.array_equal(a, b), (a, b)


def _expected_merge(labels, valid, capacity):
    """Numpy replay of the merge semantics: valid events in stream (arrival)
    order, truncated at capacity; identity rev LUT keeps labels."""
    lab = np.asarray(labels).reshape(-1)
    ok = np.asarray(valid).reshape(-1)
    kept = lab[ok][:capacity]
    dropped = int(ok.sum()) - len(kept)
    return kept, dropped


# ---------------------------------------------------------------------------
# exchange_fwd: the full single-round kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("occupancy", OCCUPANCIES)
def test_exchange_conformance(occupancy):
    state = identity_router(N_SRC)
    labels, valid = _frames(jax.random.fold_in(KEY, int(occupancy * 100)),
                            (N_SRC, CAP_IN), occupancy)
    outs = {mode: fused_exchange(labels, valid, state.fwd_tables,
                                 state.rev_tables, state.route_enables,
                                 capacity=CAPACITY, mode=mode)
            for mode in ("jax", "interpret")}
    _assert_all_equal(outs["jax"], outs["interpret"])

    # Arrival order pinned against the numpy replay, per destination: the
    # merge is src-major over the enabled sources.
    out_l, out_v, dropped = outs["jax"]
    enables = np.asarray(state.route_enables)
    for dst in range(N_SRC):
        en = enables[:, dst][:, None]
        kept, exp_drop = _expected_merge(np.asarray(labels),
                                         np.asarray(valid) & en, CAPACITY)
        got = np.asarray(out_l[dst])[np.asarray(out_v[dst])]
        assert np.array_equal(got, kept)
        assert int(dropped[dst]) == exp_drop


# ---------------------------------------------------------------------------
# exchange_stream_fwd: the multi-step kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("occupancy", OCCUPANCIES)
def test_exchange_stream_conformance(occupancy):
    n_steps = 4
    state = identity_router(N_SRC)
    labels, valid = _frames(jax.random.fold_in(KEY, 50 + int(occupancy * 100)),
                            (n_steps, N_SRC, CAP_IN), occupancy)
    outs = {mode: fused_exchange_stream(labels, valid, state.fwd_tables,
                                        state.rev_tables,
                                        state.route_enables,
                                        capacity=CAPACITY, mode=mode)
            for mode in ("jax", "interpret")}
    _assert_all_equal(outs["jax"], outs["interpret"])

    # Every timestep must equal the single-round op (stream ≡ scan of rounds).
    for t in range(n_steps):
        step = fused_exchange(labels[t], valid[t], state.fwd_tables,
                              state.rev_tables, state.route_enables,
                              capacity=CAPACITY, mode="jax")
        _assert_all_equal(tuple(o[t] for o in outs["jax"]), step)


# ---------------------------------------------------------------------------
# merge_pack_fwd: the shard_map merge, full matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("occupancy", OCCUPANCIES)
@pytest.mark.parametrize("wire16", [False, True])
@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("timed", [False, True])
def test_merge_pack_conformance(occupancy, wire16, segmented, timed):
    _check_merge_pack(occupancy, wire16, segmented, timed, 2 * CAP_IN)


@pytest.mark.parametrize("occupancy", OCCUPANCIES)
@pytest.mark.parametrize("wire16", [False, True])
@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("timed", [False, True])
def test_merge_pack_conformance_multi_tile(occupancy, wire16, segmented,
                                           timed):
    """336 events span three of the kernel's 128-event tiles, so the
    running rank base between tiles is exercised."""
    _check_merge_pack(occupancy, wire16, segmented, timed, 14 * CAP_IN)


def _check_merge_pack(occupancy, wire16, segmented, timed, n_events):
    batch = N_SRC
    key = jax.random.fold_in(
        KEY, 1000 + int(occupancy * 100) + 7 * wire16 + 13 * segmented
        + 29 * timed + (n_events - 2 * CAP_IN))
    state = identity_router(batch)
    labels, valid = _frames(key, (batch, n_events), occupancy)
    times = jnp.where(valid,
                      jax.random.randint(jax.random.fold_in(key, 2),
                                         (batch, n_events), 0, 1000), 0)
    kw = dict(capacity=CAPACITY,
              seg_lens=(n_events // 4,) * 4 if segmented else None)
    if timed:
        kw.update(times=times, queue=TIMING.queue)
    if wire16:
        stream, en = pack_wire16(labels, valid), jnp.ones_like(valid)
    else:
        stream, en = labels & WIRE_LABEL_MASK, valid
    outs = {mode: fused_merge_pack(stream, en, state.rev_tables, mode=mode,
                                   **kw)
            for mode in ("jax", "interpret")}
    _assert_all_equal(outs["jax"], outs["interpret"])

    # The wire format is transparent: int16 words ≡ int32 labels + mask.
    if wire16:
        plain = fused_merge_pack(labels & WIRE_LABEL_MASK, valid,
                                 state.rev_tables, mode="jax", **kw)
        _assert_all_equal(outs["jax"], plain)

    # Arrival order + drop counts against the numpy replay, per stream.
    out_l, out_v = outs["jax"][0], outs["jax"][1]
    dropped = outs["jax"][-1]
    for b in range(batch):
        kept, exp_drop = _expected_merge(
            np.asarray(labels[b]) & WIRE_LABEL_MASK, np.asarray(valid[b]),
            CAPACITY)
        got = np.asarray(out_l[b])[np.asarray(out_v[b])]
        assert np.array_equal(got, kept)
        assert int(dropped[b]) == exp_drop

    # Timed lane: delivered timestamps are the carried departure times plus
    # the deterministic destination queueing of each pack rank.
    if timed:
        out_t = outs["jax"][2]
        service, cc, stall = TIMING.queue
        for b in range(batch):
            src_t = np.asarray(times[b])[np.asarray(valid[b])][:CAPACITY]
            ranks = np.arange(len(src_t))
            expect = src_t + ranks * service + (ranks // cc) * stall
            got_t = np.asarray(out_t[b])[np.asarray(out_v[b])]
            assert np.array_equal(got_t, expect)


# ---------------------------------------------------------------------------
# exchange_mode="routed" vs "gather": stacked hop-graph executor (ISSUE 9)
# ---------------------------------------------------------------------------


def _routed_plan(caps, degraded):
    from repro.core import FabricSpec, LevelSpec, compile_fabric, degrade_spec

    spec = FabricSpec(levels=(LevelSpec(2, link_capacity=caps[0]),
                              LevelSpec(2, link_capacity=caps[1]),
                              LevelSpec(2, link_capacity=caps[2],
                                        extension=True)),
                      capacity=CAPACITY)
    if degraded:
        spec = degrade_spec(spec, [(1, 0)])     # dead uplink → detour
    return compile_fabric(spec)


@pytest.mark.parametrize("occupancy", OCCUPANCIES)
@pytest.mark.parametrize("caps", [(None, None, None), (6, 10, 8)])
@pytest.mark.parametrize("timed", [False, True])
@pytest.mark.parametrize("degraded", [False, True])
def test_routed_mode_conformance(occupancy, caps, timed, degraded):
    from repro.core import fabric_route_step, make_frame, with_exchange_mode

    plan = _routed_plan(caps, degraded)
    n = plan.n_nodes
    state = identity_router(n)
    key = jax.random.fold_in(KEY, 2000 + int(occupancy * 100) + 7 * timed
                             + 13 * degraded + 29 * bool(caps[0]))
    labels, valid = _frames(key, (n, CAP_IN), occupancy)
    frames, _ = make_frame(labels, jnp.zeros_like(labels) if timed else None,
                           valid, CAP_IN)
    timing = TIMING if timed else None
    outs = {mode: fabric_route_step(state, frames,
                                    with_exchange_mode(plan, mode),
                                    timing=timing)
            for mode in ("gather", "routed")}
    (g, g_d), (r, r_d) = outs["gather"], outs["routed"]
    assert jnp.array_equal(g.valid, r.valid)
    assert jnp.array_equal(jnp.where(g.valid, g.labels, 0),
                           jnp.where(r.valid, r.labels, 0))
    if timed:
        assert jnp.array_equal(jnp.where(g.valid, g.times, 0),
                               jnp.where(r.valid, r.times, 0))
    for fld in ("congestion", "uplink", "unroutable", "rerouted"):
        assert jnp.array_equal(getattr(g_d, fld), getattr(r_d, fld)), fld


def test_routed_mode_requires_concrete_enables():
    """Routed plans compile a static edge schedule — tracing the enables
    must raise, not silently fall back."""
    from repro.core import fabric_route_step, make_frame, with_exchange_mode

    plan = with_exchange_mode(_routed_plan((None, None, None), False),
                              "routed")
    state = identity_router(plan.n_nodes)
    labels, valid = _frames(KEY, (plan.n_nodes, CAP_IN), 0.5)
    frames, _ = make_frame(labels, None, valid, CAP_IN)

    import dataclasses

    def traced_enables(en):
        lvl = dataclasses.replace(plan.levels[0], enables=en)
        p = dataclasses.replace(plan,
                                levels=(lvl,) + tuple(plan.levels[1:]))
        out, _ = fabric_route_step(state, frames, p)
        return out.valid.sum()

    with pytest.raises(ValueError, match="routed"):
        jax.jit(traced_enables)(jnp.asarray(plan.levels[0].enables))
