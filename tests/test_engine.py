"""Multi-tenant emulation engine (``runtime.engine``) lifecycle battery.

The engine's contract is that S concurrent sessions batched through one
compiled window program are indistinguishable from S independent runs:

* submit/step/collect parity — spikes, all four drop fields, masked
  latency percentiles and the final per-slot plasticity row, bit for bit,
  with unequal session lengths (so tail masking is in the gate);
* evict mid-run → checkpoint → resubmit resumes bit-exactly (the stitched
  raster equals the uninterrupted run, weights included);
* slots are reused: a 1-slot engine serves a FIFO queue of sessions and
  each still matches its independent run;
* idle (masked) slots are free: they contribute zero drops and leave
  their plasticity rows untouched while neighbours run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fabric as fablib
from repro.core.aggregator import identity_router
from repro.runtime.engine import EmulationEngine
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import stream as stlib
from repro.snn.plasticity import STDPConfig

KEY = jax.random.PRNGKey(3)


def _small_network():
    chip = chiplib.ChipConfig(n_neurons=24, n_rows=12)
    cfg = netlib.NetworkConfig(n_chips=3, capacity=16, chip=chip)
    params = netlib.init_feedforward(KEY, cfg)._replace(
        router=identity_router(cfg.n_chips))
    return cfg, params


def _stims(cfg, lengths, rate=0.35, seed=7):
    rng = np.random.default_rng(seed)
    return [(rng.uniform(size=(L, cfg.chip.n_rows)) < rate)
            .astype(np.float32) for L in lengths]


def _backplane_plan(cfg):
    """A 1-level plan like the backplane's: every chip on one Aggregator,
    each chip lane admitting 2 events per round, so the uplink drops."""
    return fablib.compile_fabric(fablib.FabricSpec(
        levels=(fablib.LevelSpec(fan_in=cfg.n_chips, link_capacity=2),),
        capacity=cfg.capacity))


PLANS = {"star": lambda cfg: None, "one_level_lanes": _backplane_plan}


def _independent_run(cfg, params, stim, *, timed=False, plasticity=None,
                     plan=None):
    drives = jnp.zeros((stim.shape[0], cfg.n_chips, 1, cfg.chip.n_rows))
    drives = drives.at[:, 0, 0].set(jnp.asarray(stim))
    pstate = (netlib.init_slot_plasticity(params, 1)
              if plasticity is not None else None)
    return stlib.run_stream(params, netlib.init_state(cfg, 1), drives, cfg,
                            timed=timed, plasticity=plasticity,
                            plasticity_state=pstate, fabric=plan)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_engine_sessions_match_independent_runs(plan):
    """Batched timed+plastic sessions of unequal lengths are bit-exact
    with their independent batch-1 runs — spikes, drops, latency stats
    and the evolved per-slot weights — on the default star and on a
    1-level plan whose chip lanes drop at the uplink."""
    cfg, params = _small_network()
    plan = PLANS[plan](cfg)
    pcfg = STDPConfig()
    lengths = (10, 7, 4, 12, 9)
    stims = _stims(cfg, lengths)
    eng = EmulationEngine(params, cfg, slots=3, max_steps=max(lengths),
                          window=4, timed=True, plasticity=pcfg, plan=plan)
    sids = [eng.submit(s) for s in stims]
    eng.drain()
    total_events, uplink = 0, 0
    for sid, stim, L in zip(sids, stims, lengths):
        out = _independent_run(cfg, params, stim, timed=True,
                               plasticity=pcfg, plan=plan)
        r = eng.collect(sid)
        assert r.steps == L
        assert np.array_equal(r.spikes, np.asarray(out.spikes)[:, :, 0])
        for field in ("dropped", "uplink_dropped", "unroutable",
                      "rerouted"):
            assert getattr(r, field) == int(
                np.asarray(getattr(out, field)).sum())
        ref_lat = np.asarray(out.latency_ns)[np.asarray(out.latency_valid)]
        assert r.latency["count"] == ref_lat.size
        if ref_lat.size:
            ref = stlib.masked_latency_stats(
                ref_lat, np.ones(ref_lat.shape, bool))
            assert all(r.latency[k] == ref[k] for k in ref)
        for got, want in zip(jax.tree.leaves(r.plasticity),
                             jax.tree.leaves(out.plasticity)):
            assert np.array_equal(np.asarray(got), np.asarray(want)[:, 0])
        total_events += ref_lat.size
        uplink += r.uplink_dropped
    assert total_events > 0, "gate must see real routed traffic"
    if plan is not None:
        assert uplink > 0, "the chip lanes must drop at the uplink"


def test_timed_engine_sessions_compile_nothing_once_warm():
    """Once one session has warmed the window program and the row
    extraction, timed plastic sessions of many distinct lengths (so
    latency sample arrays of many lengths) finish with no compilation:
    the per-session latency summary runs on the host."""
    import pathlib
    import sys

    root = str(pathlib.Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.run import CompileCounter

    cfg, params = _small_network()
    eng = EmulationEngine(params, cfg, slots=3, max_steps=12, window=4,
                          timed=True, plasticity=STDPConfig())
    eng.submit(_stims(cfg, (4,), seed=11)[0])
    eng.drain()
    lengths = (3, 5, 6, 9, 11, 12, 7)
    with CompileCounter() as counter:
        sids = [eng.submit(s)
                for s in _stims(cfg, lengths, rate=0.9, seed=12)]
        eng.drain()
        results = [eng.collect(sid) for sid in sids]
    assert counter.count == 0
    assert [r.steps for r in results] == list(lengths)
    assert len({r.latency["count"] for r in results}) >= 5


def test_engine_evict_restore_is_bit_exact(tmp_path):
    """Evict mid-run checkpoints the tenant's row; resubmitting with
    ``restore_from=`` resumes bit-exactly — the stitched spike raster and
    the final weights equal the uninterrupted session's."""
    cfg, params = _small_network()
    pcfg = STDPConfig()
    stim = _stims(cfg, (12,))[0]
    eng = EmulationEngine(params, cfg, slots=2, max_steps=12, window=4,
                          plasticity=pcfg)
    sid = eng.submit(stim)
    other = eng.submit(_stims(cfg, (8,), seed=9)[0])
    eng.step()                                      # both at cursor 4
    ck = str(tmp_path / "evicted")
    partial = eng.evict(sid, ck)
    assert partial.evicted_to == ck and partial.steps == 4
    eng.drain()                                     # finish the other tenant
    eng.collect(other)
    resumed = eng.submit(stim, restore_from=ck)
    eng.drain()
    r = eng.collect(resumed)
    assert r.steps == 8                             # post-restore windows

    ref_eng = EmulationEngine(params, cfg, slots=1, max_steps=12, window=4,
                              plasticity=pcfg)
    ref_sid = ref_eng.submit(stim)
    ref_eng.drain()
    ref = ref_eng.collect(ref_sid)
    assert np.array_equal(
        np.concatenate([partial.spikes, r.spikes]), ref.spikes)
    assert np.array_equal(r.plasticity.weights, ref.plasticity.weights)


def test_engine_restore_rejects_wrong_fingerprint(tmp_path):
    """A checkpoint from a differently-configured engine must not silently
    resume: the stream fingerprint check rejects it."""
    cfg, params = _small_network()
    stim = _stims(cfg, (8,))[0]
    eng = EmulationEngine(params, cfg, slots=1, max_steps=8, window=4,
                          plasticity=STDPConfig())
    sid = eng.submit(stim)
    eng.step()
    ck = str(tmp_path / "ck")
    eng.evict(sid, ck)
    from repro.ckpt.checkpoint import CheckpointError

    other = EmulationEngine(params, cfg, slots=1, max_steps=8, window=4,
                            plasticity=STDPConfig(lr_pot=0.5))
    with pytest.raises(CheckpointError, match="fingerprint"):
        other.submit(stim, restore_from=ck)


def test_engine_slot_reuse_serves_fifo_queue():
    """A 1-slot engine drains a FIFO of 3 sessions through the same slot;
    accounting-only mode matches the keep-spikes engine's counts."""
    cfg, params = _small_network()
    lengths = (10, 7, 4)
    stims = _stims(cfg, lengths)
    eng = EmulationEngine(params, cfg, slots=1, max_steps=max(lengths),
                          window=4, keep_spikes=False)
    sids = [eng.submit(s) for s in stims]
    assert eng.active == 1 and eng.queued == 2
    eng.drain()
    got = [eng.collect(sid) for sid in sids]
    assert [r.steps for r in got] == list(lengths)
    for r, stim in zip(got, stims):
        out = _independent_run(cfg, params, stim)
        assert r.spike_count == int(np.asarray(out.spikes).sum())
        assert r.spikes is None                     # accounting-only mode


def test_engine_idle_slots_cost_nothing():
    """Slots without a session are masked out of the window program: a
    1-session engine with 3 slots produces the same result as a full one,
    and the idle slots' plasticity rows stay at their init values."""
    cfg, params = _small_network()
    pcfg = STDPConfig()
    stim = _stims(cfg, (8,))[0]
    eng = EmulationEngine(params, cfg, slots=3, max_steps=8, window=4,
                          timed=True, plasticity=pcfg)
    init_w = np.asarray(eng._plast.weights).copy()
    sid = eng.submit(stim)
    eng.drain()
    r = eng.collect(sid)
    out = _independent_run(cfg, params, stim, timed=True, plasticity=pcfg)
    assert np.array_equal(r.spikes, np.asarray(out.spikes)[:, :, 0])
    assert r.dropped == int(np.asarray(out.dropped).sum())
    # The two never-occupied slots (1, 2) kept their init weights/traces.
    final = eng._plast
    assert np.array_equal(np.asarray(final.weights)[:, 1:], init_w[:, 1:])
    assert not np.asarray(final.trace_pre)[:, 1:].any()
    assert not np.asarray(final.trace_post)[:, 1:].any()


def test_engine_rejects_bad_submissions():
    cfg, params = _small_network()
    eng = EmulationEngine(params, cfg, slots=1, max_steps=8, window=4)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(np.zeros((9, cfg.chip.n_rows), np.float32))
    with pytest.raises(ValueError, match="stimulus"):
        eng.submit(np.zeros((4, cfg.chip.n_rows + 1), np.float32))
    with pytest.raises(ValueError, match="window"):
        EmulationEngine(params, cfg, slots=1, max_steps=2, window=4)
    with pytest.raises(KeyError, match="not running"):
        eng.evict(123, "/nonexistent")
