"""End-to-end behaviour tests for the paper's system (core interconnect,
latency model, synchronization)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # property tests skip; plain tests still run
    def given(*args, **kwargs):
        return lambda fn: pytest.mark.skip(
            reason="hypothesis not installed")(fn)

    def settings(*args, **kwargs):
        return lambda fn: fn

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *args, **kwargs: None

    st = _AnyStrategy()

from repro.core import (DEFAULT_PARAMS, LINK_BANDWIDTH_OPTIMIZED,  # noqa: E402
                        LINK_LATENCY_OPTIMIZED, PROJECTED_120CHIP, SyncConfig,
                        barrier_release_time, biological_latency_ms,
                        build_fwd_table, build_rev_table, fan_in_route_enables,
                        identity_router, latency_statistics, lookup_fwd,
                        lookup_rev, make_frame, pack_words, route_step,
                        simulate_fan_in, unpack_words)
from repro.core.events import SPIKES_PER_WORD
from repro.parallel.sharding import auto_mesh

KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# Routing LUTs (hypothesis property tests)
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 2**15 - 1), min_size=1, max_size=64,
                unique=True))
def test_lut_roundtrip_preserves_enabled_labels(labels):
    """fwd(16→15) then rev(15→16) with identity tables is the identity on
    enabled labels."""
    labels = jnp.asarray(labels, jnp.int32)
    fwd = build_fwd_table(labels, labels)
    rev = build_rev_table(labels, labels)
    wire, en_f = lookup_fwd(fwd, labels)
    back, en_r = lookup_rev(rev, wire)
    assert bool(jnp.all(en_f)) and bool(jnp.all(en_r))
    assert jnp.array_equal(back, labels)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 8), st.integers(1, 40), st.integers(8, 64))
def test_aggregate_conserves_events(n_nodes, n_events, capacity):
    """Σ delivered + Σ dropped == Σ enabled-by-routes (no event creation)."""
    key = jax.random.fold_in(KEY, n_nodes * 1000 + n_events)
    labels = jax.random.randint(key, (n_nodes, n_events), 0, 2**15)
    valid = jax.random.uniform(jax.random.fold_in(key, 1),
                               (n_nodes, n_events)) < 0.7
    frames, _ = make_frame(labels, jnp.zeros_like(labels), valid, n_events)
    state = identity_router(n_nodes)
    out, dropped = route_step(state, frames, capacity)
    sent = int(frames.valid.sum())             # each event goes to n-1 peers
    expected = sent * (n_nodes - 1)
    got = int(out.valid.sum()) + int(dropped.sum())
    assert got == expected


def test_route_enables_respected():
    n = 4
    state = identity_router(n, fan_in_route_enables(n, receiver=2))
    labels = jnp.tile(jnp.arange(8, dtype=jnp.int32)[None], (n, 1))
    frames, _ = make_frame(labels, jnp.zeros_like(labels),
                           jnp.ones((n, 8), bool), 8)
    out, dropped = route_step(state, frames, capacity=64)
    counts = np.asarray(out.count())
    assert counts[2] == 3 * 8                 # fan-in target gets everything
    assert counts[[0, 1, 3]].sum() == 0       # everyone else silent
    assert int(dropped.sum()) == 0


# ---------------------------------------------------------------------------
# Layer-2 packing
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 50))
def test_pack_unpack_words_roundtrip(n_events):
    key = jax.random.fold_in(KEY, n_events)
    labels = jax.random.randint(key, (n_events,), 0, 2**16)
    valid = jax.random.uniform(jax.random.fold_in(key, 2), (n_events,)) < 0.8
    frame, _ = make_frame(labels, jnp.zeros_like(labels), valid, n_events)
    words = pack_words(frame)
    assert words.labels.shape[-1] == SPIKES_PER_WORD
    back = unpack_words(words)
    m = int(frame.valid.sum())
    assert jnp.array_equal(back.labels[:m][back.valid[:m]],
                           frame.labels[:m][frame.valid[:m]])
    assert int(back.valid.sum()) == m


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64), st.floats(0.0, 1.0))
def test_pack_unpack_words_restores_capacity(capacity, valid_frac):
    """Round-trip preserves the frame *capacity*, not just the events —
    regression for unpack silently growing frames to ceil(cap/3)*3 slots
    whenever capacity % 3 != 0."""
    key = jax.random.fold_in(KEY, capacity * 101 + int(valid_frac * 97))
    labels = jax.random.randint(key, (capacity,), 0, 2**16)
    valid = jax.random.uniform(jax.random.fold_in(key, 1),
                               (capacity,)) < valid_frac
    frame, _ = make_frame(labels, jnp.zeros_like(labels), valid, capacity)
    back = unpack_words(pack_words(frame), capacity=capacity)
    assert back.labels.shape == frame.labels.shape
    assert jnp.array_equal(back.labels, frame.labels)
    assert jnp.array_equal(back.valid, frame.valid)


def test_pack_unpack_capacity_roundtrip_regression():
    """capacity=4 (not a multiple of 3) round-trips to exactly 4 slots."""
    frame, _ = make_frame(jnp.array([7, 8, 9, 10], jnp.int32),
                          jnp.zeros((4,), jnp.int32),
                          jnp.array([True, True, True, True]), 4)
    back = unpack_words(pack_words(frame), capacity=4)
    assert back.capacity == 4
    assert jnp.array_equal(back.labels, frame.labels)
    assert jnp.array_equal(back.valid, frame.valid)
    # Without the capacity the word-aligned view keeps the padding slots.
    assert unpack_words(pack_words(frame)).capacity == 6
    with pytest.raises(ValueError):
        unpack_words(pack_words(frame), capacity=3)      # wrong word count


# ---------------------------------------------------------------------------
# Latency model — the paper's §IV/§V claims
# ---------------------------------------------------------------------------


def test_mgt_path_is_0p3us():
    assert abs(DEFAULT_PARAMS.mgt_path_ns() - 300.0) < 15.0


def test_cc_interval_single_source_of_truth():
    """The clock-compensation interval derives from the transceiver ppm
    budget in one place (link.py) and LatencyParams defaults from it —
    regression for the 1000-vs-5000 constant disagreement."""
    from repro.core.link import (cc_interval_words,
                                 clock_compensation_stall_fraction)

    assert DEFAULT_PARAMS.cc_interval == cc_interval_words()
    assert clock_compensation_stall_fraction() == pytest.approx(
        1.0 / DEFAULT_PARAMS.cc_interval)
    # The interval actually responds to the ppm budget (the old stub
    # del'd the argument).
    assert cc_interval_words(200.0) == cc_interval_words(100.0) // 2
    assert clock_compensation_stall_fraction(200.0) == pytest.approx(
        2.0 * clock_compensation_stall_fraction(100.0))


def test_cdc_is_60pct_of_non_mgt_delay():
    p = DEFAULT_PARAMS
    extra = p.fpga_to_fpga_ns() - p.mgt_path_ns()
    cdc = p.n_fpgas * p.cdc_ns_per_fpga
    assert 0.55 < cdc / extra < 0.65


@pytest.mark.slow
def test_chip_to_chip_latency_within_paper_band():
    """All rates: 0.9 µs ≤ median ≤ 1.3 µs (paper abstract / Fig 5)."""
    for rate in [1e6, 10e6, 50e6, 75e6, 83.3e6]:
        lats = simulate_fan_in(rate, 8192, jax.random.fold_in(KEY, int(rate)))
        stats = latency_statistics(lats)
        assert 850.0 <= float(stats["median_ns"]) <= 1300.0, rate
        assert float(stats["p99_ns"]) <= 1350.0, rate


@pytest.mark.slow
def test_worst_regime_jitter_about_15pct():
    lats = simulate_fan_in(83.3e6, 32768, KEY)
    stats = latency_statistics(lats)
    assert 0.08 < float(stats["jitter_frac"]) < 0.30


@pytest.mark.slow
def test_latency_discretized_to_8ns():
    lats = simulate_fan_in(10e6, 1024, KEY)
    assert jnp.allclose(jnp.mod(lats, 8.0), 0.0)


def test_second_layer_adds_about_0p4us():
    extra = DEFAULT_PARAMS.second_layer_extra_ns()
    assert 300.0 < extra < 500.0
    topo = PROJECTED_120CHIP
    same = topo.chip_to_chip_latency_ns(0, 1)
    cross = topo.chip_to_chip_latency_ns(0, 13)
    assert abs((cross - same) - extra) < 1.0
    assert topo.transceiver_hops(0, 13) == 4


def test_projected_system_size():
    assert PROJECTED_120CHIP.n_neurons > 61_000
    assert PROJECTED_120CHIP.n_synapses > 15_000_000


def test_link_encoding_tradeoff():
    """8b10b@5G has lower word latency than 64b66b@8G despite lower rate
    (the paper's §III design decision)."""
    lat = LINK_LATENCY_OPTIMIZED
    bw = LINK_BANDWIDTH_OPTIMIZED
    assert lat.word_serialization_ns() < bw.word_serialization_ns()
    assert bw.payload_rate_gbps() > lat.payload_rate_gbps()


def test_speedup_tradeoff_fig5b():
    """At 1000× the routing latency is ~an order of magnitude below
    biological membrane time constants (10–30 ms)."""
    lat_bio = float(biological_latency_ms(1000.0))
    assert 0.5 < lat_bio < 2.0


# ---------------------------------------------------------------------------
# Synchronization barrier
# ---------------------------------------------------------------------------


def test_barrier_releases_on_last_participant():
    cfg = SyncConfig(n_participants=4, timeout_cycles=1000)
    release, timed_out = barrier_release_time(jnp.array([10, 500, 40, 3]), cfg)
    assert int(release) == 500 and not bool(timed_out)


def test_barrier_timeout_recovery():
    cfg = SyncConfig(n_participants=4, timeout_cycles=1000)
    release, timed_out = barrier_release_time(jnp.array([10, -1, 40, 3]), cfg)
    assert bool(timed_out) and int(release) == 1000


def test_barrier_in_graph():
    from repro.core.sync import barrier

    mesh = auto_mesh((1,), ("chip",))
    fn = jax.jit(jax.shard_map(
        lambda r: barrier(r[0], "chip")[None],
        mesh=mesh, in_specs=jax.sharding.PartitionSpec("chip"),
        out_specs=jax.sharding.PartitionSpec("chip")))
    assert bool(fn(jnp.array([True]))[0])
    assert not bool(fn(jnp.array([False]))[0])
