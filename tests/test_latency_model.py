"""Latency-model conformance battery (ISSUE 4 satellite).

Promotes the Fig 5 paper-band checks out of ``benchmarks/fig5_latency.py``
into tier-1 — chip-level medians inside the paper's 0.9–1.3 µs band at every
rate, 8 ns measurement discretization, worst-regime jitter ≈ 15 % — and pins
the properties the timed streaming datapath relies on:

* the closed-form per-hop queue terms (``queue_wait_ns`` / ``hop_delays``)
  equal the Lindley-recursion simulator on a window of simultaneous
  arrivals, bit-for-bit;
* queueing is monotone non-decreasing in occupancy and in spike rate;
* at zero congestion the end-to-end delay is exactly the closed-form sum of
  the fixed per-stage terms (``timed_wire``);
* the simulator is deterministic: same key → bit-identical samples.
"""

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

from repro.core import (DEFAULT_PARAMS, PAPER_BAND_NS,  # noqa: E402
                        PAPER_JITTER_FRAC, LatencyParams, hop_delays,
                        latency_statistics, queue_wait_ns, simulate_fan_in,
                        timed_wire)
from repro.core.latency import (MGT_CLOCK_NS,  # noqa: E402
                                SYSTEM_CLOCK_NS, _lindley_queue)

KEY = jax.random.key(4)

# The Fig 5 per-sender rate ladder (3:1 fan-in; 83.3 MHz saturates the
# 250 MHz aggregate event rate of the receiving lane).
RATES_HZ = (1e6, 5e6, 10e6, 25e6, 50e6, 70e6, 80e6, 83.3e6)
# Reduced sample count for the per-rate tier-1 sweep (paper: 2^15); the
# worst-regime jitter claim needs the full backlog build-up and keeps 2^15.
N_SPIKES_FAST = 2 ** 12


def _chip_lats(rate_hz, n_spikes):
    return simulate_fan_in(rate_hz, n_spikes,
                           jax.random.fold_in(KEY, int(rate_hz)),
                           fan_in=3, level="chip")


# ---------------------------------------------------------------------------
# Fig 5 paper-band checks, promoted from benchmarks/fig5_latency.py
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rate_hz", RATES_HZ)
def test_chip_level_median_in_paper_band(rate_hz):
    """Chip-to-chip median latency stays within 0.9–1.3 µs at every rate
    (§IV headline claim; band constants shared with the benchmark)."""
    lats = _chip_lats(rate_hz, N_SPIKES_FAST)
    med = float(jnp.median(lats))
    lo, hi = PAPER_BAND_NS
    assert lo <= med <= hi, f"median {med} ns outside [{lo}, {hi}] ns"


def test_latencies_quantized_to_system_clock():
    """Fig 5 histograms are discretized at the 8 ns system clock."""
    for rate_hz in (1e6, 83.3e6):
        lats = np.asarray(_chip_lats(rate_hz, N_SPIKES_FAST))
        assert np.all(lats % SYSTEM_CLOCK_NS == 0)


@pytest.mark.slow
def test_worst_regime_jitter_about_fifteen_percent():
    """At link saturation (83.3 MHz × 3 senders = 250 MHz aggregate) the
    total jitter reaches ≈ 15 % of the median — needs the paper's full 2^15
    samples for the congestion backlog to build up."""
    lats = _chip_lats(83.3e6, 2 ** 15)
    stats = {k: float(v) for k, v in latency_statistics(lats).items()}
    assert PAPER_BAND_NS[0] <= stats["median_ns"] <= PAPER_BAND_NS[1]
    assert 0.66 * PAPER_JITTER_FRAC <= stats["jitter_frac"] \
        <= 1.66 * PAPER_JITTER_FRAC, stats


def test_chip_medians_monotone_in_rate():
    """Across the Fig 5 ladder the median latency never *decreases* with
    rate by more than one measurement clock tick (congestion only adds)."""
    meds = [float(jnp.median(_chip_lats(r, N_SPIKES_FAST))) for r in RATES_HZ]
    for lo, hi in zip(meds, meds[1:]):
        assert hi >= lo - SYSTEM_CLOCK_NS, meds


# ---------------------------------------------------------------------------
# Closed-form per-hop queue terms vs the Lindley simulator
# ---------------------------------------------------------------------------


def test_hop_delays_match_lindley_on_simultaneous_arrivals():
    """``hop_delays``'s mux term is the Lindley recursion evaluated on a
    window of simultaneous arrivals — the exact identity the timed datapath
    exploits to fold queueing into the pack rank."""
    n = 2500            # crosses two clock-compensation intervals
    lindley = _lindley_queue(jnp.zeros((n,)), MGT_CLOCK_NS,
                             DEFAULT_PARAMS.cc_interval,
                             DEFAULT_PARAMS.cc_stall_ns)
    closed = hop_delays(DEFAULT_PARAMS, jnp.arange(n)).mux_ns
    assert jnp.array_equal(lindley, closed)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 10_000))
def test_queue_wait_monotone_in_occupancy(r1, r2):
    """Property: every hop's wait is monotone non-decreasing in rank."""
    lo, hi = sorted((r1, r2))
    d_lo = hop_delays(DEFAULT_PARAMS, jnp.int32(lo))
    d_hi = hop_delays(DEFAULT_PARAMS, jnp.int32(hi))
    for a, b in zip(d_lo, d_hi):
        assert float(a) <= float(b)
    assert float(d_lo.total_ns) <= float(d_hi.total_ns)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(RATES_HZ), st.sampled_from(RATES_HZ))
def test_queue_wait_monotone_in_rate(r1, r2):
    """Property: the mean Lindley wait of a regular merged train is monotone
    non-decreasing in the aggregate spike rate (the queueing component of
    Fig 5, isolated from jitter compensation)."""
    lo, hi = sorted((r1, r2))
    n = 512

    def mean_wait(rate_hz):
        arrivals = (jnp.arange(n, dtype=jnp.float32)
                    * (1e9 / (3.0 * rate_hz)))        # 3:1 fan-in
        return float(jnp.mean(_lindley_queue(
            arrivals, MGT_CLOCK_NS, DEFAULT_PARAMS.cc_interval,
            DEFAULT_PARAMS.cc_stall_ns)))

    assert mean_wait(lo) <= mean_wait(hi) + 1e-4


# ---------------------------------------------------------------------------
# Zero congestion ⇒ closed-form fixed path; determinism
# ---------------------------------------------------------------------------


def test_zero_congestion_is_closed_form_fixed_sum():
    """Rank 0 pays no queueing anywhere, so the timed wire's end-to-end
    delay collapses to the closed-form sum of fixed per-stage terms."""
    d = hop_delays(DEFAULT_PARAMS, jnp.zeros((4,), jnp.int32))
    for term in d:
        assert jnp.array_equal(term, jnp.zeros((4,)))
    w = timed_wire(DEFAULT_PARAMS)
    assert (w.sender_fixed_ns + w.recv_fixed_ns
            == round(DEFAULT_PARAMS.chip_to_chip_ns()))
    wf = timed_wire(DEFAULT_PARAMS, level="fpga")
    assert (wf.sender_fixed_ns + wf.recv_fixed_ns
            == round(DEFAULT_PARAMS.sender_fixed_ns("fpga")
                     + DEFAULT_PARAMS.recv_fixed_ns("fpga")))


@settings(max_examples=10, deadline=None)
@given(st.floats(16.0, 2000.0), st.floats(1.0, 500.0))
def test_fixed_path_split_sums_to_chip_to_chip(l2_ns, on_chip_ns):
    """Property: sender_fixed + recv_fixed == chip_to_chip for any
    calibration — the split cannot drift from the §IV total."""
    p = LatencyParams(l2_link_ns=l2_ns, on_chip_ns=on_chip_ns)
    assert (p.sender_fixed_ns("chip") + p.recv_fixed_ns("chip")
            == pytest.approx(p.chip_to_chip_ns()))


def test_simulator_deterministic_same_key():
    """Same key → bit-identical samples; a different key differs (the
    deterministic-delay property the wire format relies on)."""
    k = jax.random.fold_in(KEY, 77)
    a = simulate_fan_in(25e6, 1024, k, fan_in=3, level="chip")
    b = simulate_fan_in(25e6, 1024, k, fan_in=3, level="chip")
    assert jnp.array_equal(a, b)
    c = simulate_fan_in(25e6, 1024, jax.random.fold_in(KEY, 78),
                        fan_in=3, level="chip")
    assert not jnp.array_equal(a, c)


def test_timed_wire_rejects_unknown_level():
    with pytest.raises(ValueError):
        timed_wire(DEFAULT_PARAMS, level="rack")
