"""Timed streaming datapath (ISSUE 4 tentpole).

The per-event timestamp lane threaded through ``run_stream`` makes the
Fig 5 timing model and the functional datapath one program; this battery
pins:

* the paper's headline claim *from the datapath itself*: driving the Fig 5
  measurement setup (3 senders → 1 receiver, regular trains) through the
  timed ``run_stream`` lands the chip-to-chip median inside 0.9–1.3 µs at
  every rate of the Fig 5 ladder;
* zero congestion ⇒ the closed-form fixed path, exactly;
* timestamps are bit-exact between the jnp oracle and the Pallas
  (interpret) kernel path, at the exchange level;
* the timed run is functionally invariant: spikes / drops / final state
  identical to the untimed run, and the uplink compact-before-gather
  stages do not perturb timestamps (capacity parity extends to the lane);
* a golden 4-chip fixture catches silent bit-drift (``--regen-golden``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from repro.core import (EventFrame, full_route_enables, identity_router,
                        make_frame, route_step, route_step_hierarchical,
                        timed_wire, PAPER_BAND_NS)
from repro.core.routing import fan_in_route_enables
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import stream as stlib
from repro.snn import init_feedforward
from repro.snn.chip import ChipParams
from repro.snn.neuron import NeuronParams

KEY = jax.random.key(41)
TIMING = timed_wire()

# ---------------------------------------------------------------------------
# Fig 5 measurement setup on the real datapath: 3 senders → 1 receiver
# ---------------------------------------------------------------------------

N_CHIPS, RECEIVER = 4, 3
N_ROWS = chiplib.N_SYNAPSE_ROWS
# Simulation window: fine enough that a window's traffic always drains
# before the next one (the frame-synchronous queue model carries no
# backlog); at link saturation 250 MHz × 0.25 µs ≈ 63 events/window.
DT_US = 0.25
N_STEPS = 64
RATES_HZ = (1e6, 5e6, 10e6, 25e6, 50e6, 70e6, 80e6, 83.3e6)


def _fig5_cfg() -> netlib.NetworkConfig:
    # Short synaptic time constant: one driven row ⇒ exactly one spike in
    # that window, no residual-current tail — deterministic rate control.
    return netlib.NetworkConfig(
        n_chips=N_CHIPS, capacity=256, dt_us=DT_US,
        chip=chiplib.ChipConfig(neuron=NeuronParams(tau_syn_us=0.2)))


def _fig5_params(cfg: netlib.NetworkConfig) -> netlib.NetworkParams:
    """Row r drives neuron r just above threshold: spikes == driven rows."""
    diag = jnp.zeros((N_ROWS, cfg.chip.n_neurons)).at[
        jnp.arange(N_ROWS), jnp.arange(N_ROWS)].set(63.0)
    chips = ChipParams(
        weights=jnp.broadcast_to(diag, (N_CHIPS, *diag.shape)),
        row_sign=jnp.ones((N_CHIPS, N_ROWS)),
        w_scale=jnp.full((N_CHIPS,), 12.0 / 63.0))
    return netlib.NetworkParams(
        chips=chips,
        row_of_label=jnp.full((N_CHIPS, 1 << 16), -1, jnp.int32),
        router=identity_router(N_CHIPS,
                               fan_in_route_enables(N_CHIPS, RECEIVER)))


def _regular_drives(rate_hz: float) -> jax.Array:
    """Regular spike trains at ``rate_hz`` per sender: ⌊(t+1)ε⌋ − ⌊tε⌋
    events in window t (exact long-run rate, fractional rates included),
    round-robin over rows so every driven row spikes exactly once."""
    eps = rate_hz * DT_US * 1e-6
    edges = np.floor((np.arange(N_STEPS + 1)) * eps).astype(int)
    counts = np.diff(edges)
    d = np.zeros((N_STEPS, N_CHIPS, 1, N_ROWS), np.float32)
    off = 0
    for t in range(N_STEPS):
        rows = (off + np.arange(counts[t])) % N_ROWS
        d[t, :RECEIVER, 0, rows] = 1.0
        off += counts[t]
    return jnp.asarray(d)


@pytest.fixture(scope="module")
def fig5_run():
    cfg = _fig5_cfg()
    params = _fig5_params(cfg)
    state = netlib.init_state(cfg, 1)
    fn = jax.jit(lambda st, d: stlib.run_stream(params, st, d, cfg,
                                                mode="event", timed=True))
    return lambda drives: fn(state, drives)


@pytest.mark.parametrize("rate_hz", RATES_HZ)
def test_timed_stream_median_in_paper_band(fig5_run, rate_hz):
    """Acceptance: medians from the timed datapath land in the paper's
    0.9–1.3 µs band at every Fig 5 rate — the band assertion is a pinned
    invariant of the *stream*, not only of the standalone model."""
    out = fig5_run(_regular_drives(rate_hz))
    assert int(out.dropped.sum()) == 0          # band measured loss-free
    stats = stlib.stream_latency_stats(out)
    lo, hi = PAPER_BAND_NS
    assert lo <= stats["median_ns"] <= hi, (rate_hz, stats)
    # Everything the receiver saw sits in the band too (p99 included):
    # congestion at these rates never exceeds the paper's envelope.
    assert stats["p99_ns"] <= hi, (rate_hz, stats)


def test_timed_stream_median_grows_with_rate(fig5_run):
    """Congestion only adds: the median is monotone over the rate ladder."""
    meds = [stlib.stream_latency_stats(fig5_run(_regular_drives(r)))
            ["median_ns"] for r in RATES_HZ]
    assert all(b >= a for a, b in zip(meds, meds[1:])), meds


def test_single_event_is_exactly_the_fixed_path(fig5_run):
    """Zero congestion, end to end: one spike in one window arrives exactly
    ``sender_fixed + recv_fixed`` ns later (== chip_to_chip_ns)."""
    d = np.zeros((N_STEPS, N_CHIPS, 1, N_ROWS), np.float32)
    d[3, 0, 0, 7] = 1.0                          # one row, one sender, once
    out = fig5_run(jnp.asarray(d))
    lats = np.asarray(out.latency_ns)[np.asarray(out.latency_valid)]
    assert lats.shape == (1,)
    assert int(lats[0]) == TIMING.sender_fixed_ns + TIMING.recv_fixed_ns


# ---------------------------------------------------------------------------
# Oracle vs Pallas(interpret) timestamp parity at the exchange level
# ---------------------------------------------------------------------------


def _busy_frames(key, n, cap_in, occupancy=0.6):
    labels = jax.random.randint(key, (n, cap_in), 0, 2 ** 15)
    valid = jax.random.uniform(jax.random.fold_in(key, 1),
                               (n, cap_in)) < occupancy
    times = jnp.where(valid, jax.random.randint(jax.random.fold_in(key, 2),
                                                (n, cap_in), 0, 1000), 0)
    frames, _ = make_frame(labels, times, valid, cap_in)
    return frames


@pytest.mark.parametrize("topology", ["star", "hierarchical"])
def test_timed_exchange_oracle_matches_interpret(monkeypatch, topology):
    """The full timed round — fwd LUT, uplink lane wait, merge with
    in-kernel queueing, rev LUT, receiver fixed path — is bit-exact between
    the jnp oracle and the Pallas interpreter, timestamps included."""
    frames = _busy_frames(jax.random.fold_in(KEY, 1), 4, 24)
    state = identity_router(4)

    def round_fn():
        if topology == "star":
            return route_step(state, frames, 16, timing=TIMING,
                              use_fused=True)
        return route_step_hierarchical(
            state, frames, 16, n_pods=2,
            intra_enables=full_route_enables(2),
            inter_enables=full_route_enables(2),
            link_capacity=12, pod_capacity=30, timing=TIMING,
            use_fused=True)

    outs = {}
    for mode in ("jax", "interpret"):
        monkeypatch.setattr(repro.kernels, "default_mode", lambda m=mode: m)
        outs[mode] = round_fn()
    (o_j, d_j), (o_i, d_i) = outs["jax"], outs["interpret"]
    assert jnp.array_equal(o_j.times, o_i.times)
    assert jnp.array_equal(o_j.labels, o_i.labels)
    assert jnp.array_equal(o_j.valid, o_i.valid)
    for a, b in zip(jax.tree.leaves(d_j), jax.tree.leaves(d_i)):
        assert jnp.array_equal(a, b)


def test_timed_uplink_stages_do_not_perturb_timestamps():
    """Capacity parity extends to the lane: with the compact-before-gather
    stages at ≥ raw sizes, timestamps are bit-exact with the dense round
    (uplink waits are computed from egress ranks, not pack layout)."""
    n_pods, per, cap_in = 2, 3, 20
    state = identity_router(n_pods * per)
    frames = _busy_frames(jax.random.fold_in(KEY, 2), n_pods * per, cap_in,
                          occupancy=0.4)
    kw = dict(n_pods=n_pods, intra_enables=full_route_enables(per),
              inter_enables=full_route_enables(n_pods), timing=TIMING)
    ref, d_ref = route_step_hierarchical(state, frames, 16, **kw)
    for caps in (dict(link_capacity=cap_in),
                 dict(pod_capacity=per * cap_in),
                 dict(link_capacity=cap_in, pod_capacity=per * cap_in)):
        out, d = route_step_hierarchical(state, frames, 16, **kw, **caps)
        assert jnp.array_equal(out.times, ref.times), caps
        assert jnp.array_equal(out.labels, ref.labels)
        assert jnp.array_equal(d.congestion, d_ref.congestion)


def test_inter_backplane_events_pay_second_layer_extra():
    """A lone inter-pod event arrives exactly ``second_layer_extra_ns``
    later than a lone intra-pod event (§V's projected +0.4 µs)."""
    state = identity_router(4)
    labels = jnp.zeros((4, 8), jnp.int32).at[0, 0].set(9)
    valid = jnp.zeros((4, 8), bool).at[0, 0].set(True)
    frames = EventFrame(labels=labels, times=jnp.zeros_like(labels),
                        valid=valid)
    out, _ = route_step_hierarchical(
        state, frames, 16, n_pods=2, intra_enables=full_route_enables(2),
        inter_enables=full_route_enables(2), timing=TIMING)
    intra_t = int(out.times[1][out.valid[1]][0])     # same pod as sender
    inter_t = int(out.times[2][out.valid[2]][0])     # other pod
    assert intra_t == TIMING.sender_fixed_ns + TIMING.recv_fixed_ns
    assert inter_t - intra_t == TIMING.second_layer_extra_ns


# ---------------------------------------------------------------------------
# run_stream: timed ≡ untimed on every functional observable
# ---------------------------------------------------------------------------


def _stim_drives(key, n_steps, n_chips, batch, n_rows, p=0.4):
    drives = jnp.zeros((n_steps, n_chips, batch, n_rows))
    stim = (jax.random.uniform(key, (n_steps, batch, n_rows)) < p).astype(
        jnp.float32)
    return drives.at[:, 0].set(stim)


@pytest.mark.parametrize("topology", ["star", "hierarchical"])
def test_run_stream_timed_functionally_invariant(topology):
    cfg = netlib.NetworkConfig(n_chips=4, capacity=64)   # tight → drops
    params = init_feedforward(KEY, cfg)
    drives = _stim_drives(jax.random.fold_in(KEY, 3), 6, 4, 2,
                          cfg.chip.n_rows)
    state = netlib.init_state(cfg, 2)
    kw = dict(mode="event")
    if topology == "hierarchical":
        kw.update(topology="hierarchical", n_pods=2,
                  intra_enables=full_route_enables(2),
                  inter_enables=full_route_enables(2))
    ref = stlib.run_stream(params, state, drives, cfg, **kw)
    out = stlib.run_stream(params, state, drives, cfg, **kw, timed=True)
    assert jnp.array_equal(out.spikes, ref.spikes)
    assert jnp.array_equal(out.dropped, ref.dropped)
    assert jnp.array_equal(out.uplink_dropped, ref.uplink_dropped)
    assert jnp.array_equal(out.state.inflight, ref.state.inflight)
    assert ref.latency_ns.shape[-1] == 0         # untimed: zero-width lane
    assert out.latency_ns.shape[-1] == cfg.capacity
    assert bool(out.latency_valid.any())
    # Padding slots carry 0; delivered latencies are at least the fixed path.
    lat = np.asarray(out.latency_ns)
    lv = np.asarray(out.latency_valid)
    assert np.all(lat[~lv] == 0)
    assert np.all(lat[lv] >= TIMING.sender_fixed_ns + TIMING.recv_fixed_ns)


def test_run_stream_timed_rejects_dense_mode():
    cfg = netlib.NetworkConfig(n_chips=2)
    params = init_feedforward(KEY, cfg)
    state = netlib.init_state(cfg, 1)
    drives = jnp.zeros((2, 2, 1, cfg.chip.n_rows))
    with pytest.raises(ValueError, match="timed"):
        stlib.run_stream(params, state, drives, cfg, mode="dense",
                         route_mats=jnp.zeros(
                             (2, 2, cfg.chip.n_neurons, cfg.chip.n_rows)),
                         timed=True)


def test_stream_latency_stats_requires_timed_run():
    cfg = netlib.NetworkConfig(n_chips=2)
    params = init_feedforward(KEY, cfg)
    state = netlib.init_state(cfg, 1)
    drives = jnp.zeros((2, 2, 1, cfg.chip.n_rows))
    out = stlib.run_stream(params, state, drives, cfg, mode="event")
    with pytest.raises(ValueError, match="timed"):
        stlib.stream_latency_stats(out)


def test_latency_stats_strict_raises_on_zero_events():
    """Zero delivered events stays an error under strict=True — both for a
    timed run with no traffic and for the raw masked reduction."""
    empty = jnp.zeros((3, 2, 1, 4), jnp.int32)
    none_valid = jnp.zeros((3, 2, 1, 4), bool)
    with pytest.raises(ValueError, match="delivered"):
        stlib.masked_latency_stats(empty, none_valid)
    cfg = netlib.NetworkConfig(n_chips=2)
    params = init_feedforward(KEY, cfg)
    out = stlib.run_stream(params, netlib.init_state(cfg, 1),
                           jnp.zeros((2, 2, 1, cfg.chip.n_rows)), cfg,
                           timed=True)
    with pytest.raises(ValueError, match="delivered"):
        stlib.stream_latency_stats(out)


def test_latency_stats_non_strict_zero_events_returns_nan_and_count():
    """strict=False keeps per-tenant accounting total on idle sessions:
    every percentile key is NaN, ``count`` is 0, and nothing raises."""
    stats = stlib.masked_latency_stats(jnp.zeros((5,), jnp.int32),
                                       jnp.zeros((5,), bool), strict=False)
    assert stats["count"] == 0
    assert set(stats) == {"median_ns", "p01_ns", "p99_ns", "jitter_ns",
                          "jitter_frac", "count"}
    for k, v in stats.items():
        if k != "count":
            assert np.isnan(v), f"{k} should be NaN with zero events"
    # With events, strict and non-strict agree and count the samples.
    lats = jnp.asarray([100, 200, 300, 400], jnp.int32)
    valid = jnp.asarray([True, True, False, True])
    loose = stlib.masked_latency_stats(lats, valid, strict=False)
    tight = stlib.masked_latency_stats(lats, valid)
    assert loose == tight and loose["count"] == 3
    assert loose["median_ns"] == 200.0


@pytest.mark.parametrize("n", [1, 2, 5, 101, 4096])
def test_latency_stats_are_the_float64_percentiles(n):
    """The summary is NumPy's linear-interpolated percentiles at 1, 50 and
    99 of the valid samples in float64, exactly, for device and host
    inputs alike."""
    rng = np.random.default_rng(n)
    lats = rng.integers(700, 2400, size=(n, 3), dtype=np.int32)
    valid = rng.random((n, 3)) < 0.7
    valid[0, 0] = True
    x = lats[valid].astype(np.float64)
    p01, med, p99 = np.percentile(x, [1.0, 50.0, 99.0])
    want = {"median_ns": med, "p01_ns": p01, "p99_ns": p99,
            "jitter_ns": p99 - p01, "jitter_frac": (p99 - p01) / med,
            "count": x.size}
    assert stlib.masked_latency_stats(lats, valid) == want
    assert stlib.masked_latency_stats(jnp.asarray(lats),
                                      jnp.asarray(valid)) == want
    stats = stlib.masked_latency_stats(lats, valid, strict=False)
    assert stats == want and isinstance(stats["count"], int)


# ---------------------------------------------------------------------------
# Golden regression fixture (see conftest.py: --regen-golden)
# ---------------------------------------------------------------------------


def _golden_arrays() -> dict[str, np.ndarray]:
    """A small, fully deterministic 4-chip timed run: one hierarchical
    exchange round (labels / pack order / timestamps / split drop counts)
    plus a closed-loop timed stream (spikes + latency lane)."""
    frames = _busy_frames(jax.random.fold_in(KEY, 99), 4, 16, occupancy=0.5)
    state = identity_router(4)
    round_out, drops = route_step_hierarchical(
        state, frames, 12, n_pods=2, intra_enables=full_route_enables(2),
        inter_enables=full_route_enables(2), link_capacity=8,
        pod_capacity=12, timing=TIMING)

    cfg = netlib.NetworkConfig(n_chips=4, capacity=48)
    params = init_feedforward(jax.random.fold_in(KEY, 100), cfg)
    drives = _stim_drives(jax.random.fold_in(KEY, 101), 5, 4, 1,
                          cfg.chip.n_rows, p=0.5)
    stream = stlib.run_stream(params, netlib.init_state(cfg, 1), drives,
                              cfg, mode="event", timed=True)
    return {
        "round_labels": np.asarray(round_out.labels),
        "round_valid": np.asarray(round_out.valid),
        "round_times": np.asarray(round_out.times),
        "round_congestion": np.asarray(drops.congestion),
        "round_uplink": np.asarray(drops.uplink),
        "stream_spikes": np.asarray(stream.spikes),
        "stream_dropped": np.asarray(stream.dropped),
        "stream_latency_ns": np.asarray(stream.latency_ns),
        "stream_latency_valid": np.asarray(stream.latency_valid),
    }


def test_timed_stream_matches_golden_fixture(golden_path, regen_golden):
    """Bit-exact against the frozen run — catches silent drift in future
    datapath refactors.  Regenerate deliberately with
    ``pytest --regen-golden tests/test_timed_stream.py``."""
    path = golden_path("timed_stream_4chip.npz")
    arrays = _golden_arrays()
    if regen_golden:
        path.parent.mkdir(parents=True, exist_ok=True)
        np.savez_compressed(path, **arrays)
        pytest.skip(f"regenerated {path}")
    assert path.exists(), (
        f"golden fixture {path} missing — run pytest --regen-golden")
    golden = np.load(path)
    assert set(golden.files) == set(arrays)
    for name, got in arrays.items():
        want = golden[name]
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert np.array_equal(got, want), f"bit-drift in {name}"
