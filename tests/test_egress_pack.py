"""The chips' egress pack through the Pallas pack kernel.

``spike_router.ops.pack_frame`` must give what ``events.make_frame`` gives
(labels, times, valid, drop counts, zero-filled padding) and agree with the
seed's argsort scheme on the masked observables; ``run_stream`` must be
bit-identical whether its egress packs in the kernel or in jnp.
"""

import jax
import jax.numpy as jnp
import pytest

import repro.kernels
from repro.analysis import scenarios
from repro.core import FaultEvent, make_frame, make_frame_argsort
from repro.kernels.spike_router.ops import pack_frame
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import plasticity as plaslib
from repro.snn import stream as stlib

KEY = jax.random.key(14)

# (leading shape, n_events, capacity): the cells' egress (N = 512 neurons
# to C = 128 and to C = 96) with fewer rows, 20 of them (no multiple of 8),
# and a stream shorter than its capacity.
SHAPES = [((20,), 512, 128), ((4, 5), 512, 96), ((5,), 40, 64)]
DENSITIES = [0.0, 0.05, 0.5, 1.0]


def _events(shape, density):
    key = jax.random.fold_in(KEY, hash((shape, density)) % 2**30)
    labels = jax.random.randint(key, shape, 0, 2**16)
    valid = jax.random.uniform(jax.random.fold_in(key, 1), shape) < density
    return labels, valid


def _assert_same(a, b):
    (fa, da), (fb, db) = a, b
    for x, y in zip((*fa, da), (*fb, db)):
        assert x.dtype == y.dtype and jnp.array_equal(x, y)


@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("shape", SHAPES)
def test_pack_frame_kernel_matches_make_frame(shape, density):
    lead, n, cap = shape
    labels, valid = _events((*lead, n), density)
    got = pack_frame(labels, valid, capacity=cap, mode="interpret")
    _assert_same(got, make_frame(labels, None, valid, cap))
    frame, dropped = got
    assert not frame.times.any()
    # The seed's argsort scheme, compared on the slots it fills.
    ref, ref_dropped = make_frame_argsort(labels, jnp.zeros_like(labels),
                                          valid, cap)
    assert jnp.array_equal(frame.valid, ref.valid)
    assert jnp.array_equal(frame.labels, jnp.where(ref.valid, ref.labels, 0))
    assert jnp.array_equal(dropped, ref_dropped)
    assert jnp.array_equal(dropped,
                           jnp.maximum(valid.sum(axis=-1) - cap, 0))
    if density == 1.0 and n > cap:
        assert (dropped == n - cap).all()


def test_pack_frame_jax_mode_is_make_frame():
    labels, valid = _events((3, 64), 0.5)
    _assert_same(pack_frame(labels, valid, capacity=16, mode="jax"),
                 make_frame(labels, None, valid, 16))
    with pytest.raises(ValueError, match="mode"):
        pack_frame(labels, valid, capacity=16, mode="bogus")


# ---------------------------------------------------------------------------
# run_stream: the egress in the kernel against the egress in jnp
# ---------------------------------------------------------------------------

SMALL = chiplib.ChipConfig(n_neurons=64, n_rows=32)


@pytest.fixture
def egress_kernel(monkeypatch):
    """Pack the egress in the interpreted kernel; leave every fabric merge
    on the mode it would take."""
    real = stlib.pack_frame

    def kernel(*args, **kw):
        return real(*args, **{**kw, "mode": "interpret"})

    def install():
        monkeypatch.setattr(stlib, "pack_frame", kernel)
    return install


def _stim(key, n_steps, n_chips, batch, n_rows, p):
    return (jax.random.uniform(key, (n_steps, n_chips, batch, n_rows)) < p
            ).astype(jnp.float32)


def _assert_streams_equal(a, b):
    for name in ("spikes", "dropped", "uplink_dropped", "unroutable",
                 "rerouted", "latency_ns", "latency_valid"):
        assert jnp.array_equal(getattr(a, name), getattr(b, name)), name
    assert jax.tree.all(jax.tree.map(jnp.array_equal, a.state, b.state))
    assert jax.tree.all(jax.tree.map(jnp.array_equal, a.plasticity,
                                     b.plasticity))


def test_run_stream_timed_shared_egress_kernel_bit_identical(egress_kernel):
    """Shared-weight scan body, timed, on a 2-level fabric with tight
    lanes and a dead uplink: every drop field is exercised."""
    cap = 16
    fan_ins = (4, 2)
    plan = scenarios.plan_for(fan_ins, cap, (6, 10))
    cfg = netlib.NetworkConfig(n_chips=8, capacity=cap, chip=SMALL)
    params = netlib.init_feedforward(KEY, cfg)
    drives = _stim(jax.random.fold_in(KEY, 1), 6, 8, 3, SMALL.n_rows, 0.6)
    state = netlib.init_state(cfg, 3)
    faults = [FaultEvent(level=1, edge=0, kill_step=1, restore_step=5)]

    def run(p, s, d):
        return stlib.run_stream(p, s, d, cfg, fabric=plan, timed=True,
                                faults=faults,
                                plasticity=plaslib.STDPConfig(),
                                use_fused=False)

    ref = jax.jit(run)(params, state, drives)
    egress_kernel()
    got = jax.jit(run)(params, state, drives)
    _assert_streams_equal(got, ref)
    assert int(ref.dropped.sum()) > 0 and int(ref.uplink_dropped.sum()) > 0
    assert int(ref.unroutable.sum()) > 0 and bool(ref.latency_valid.any())


def test_run_stream_slot_plasticity_egress_kernel_bit_identical(
        egress_kernel):
    """Per-slot scan body (the engine's): ``SlotPlasticityState`` and a
    slot mask, on a star whose capacity the egress overflows."""
    cfg = netlib.NetworkConfig(n_chips=3, capacity=12, chip=SMALL)
    params = netlib.init_feedforward(KEY, cfg)
    drives = _stim(jax.random.fold_in(KEY, 2), 6, 3, 4, SMALL.n_rows, 0.6)
    state = netlib.init_state(cfg, 4)
    mask = jnp.arange(6)[:, None] < jnp.array([6, 4, 6, 2])[None]

    def run(p, s, d):
        return stlib.run_stream(
            p, s, d, cfg, plasticity=plaslib.STDPConfig(lr_pot=0.5),
            plasticity_state=plaslib.init_slot_stdp(p.chips.weights, 4),
            slot_mask=mask, use_fused=False)

    ref = jax.jit(run)(params, state, drives)
    egress_kernel()
    got = jax.jit(run)(params, state, drives)
    _assert_streams_equal(got, ref)
    assert isinstance(got.plasticity, plaslib.SlotPlasticityState)
    assert int(ref.dropped.sum()) > 0


@pytest.mark.parametrize("use_fused, default, expect", [
    (None, "interpret", "interpret"), (True, "pallas", "pallas"),
    (False, "pallas", "jax"), (None, "jax", "jax"),
])
def test_egress_mode_follows_use_fused(monkeypatch, use_fused, default,
                                       expect):
    """The egress takes the mode the fabric's merges take."""
    seen = []
    monkeypatch.delenv("REPRO_FUSED_EXCHANGE", raising=False)
    monkeypatch.setattr(repro.kernels, "default_mode", lambda: default)
    monkeypatch.setattr(stlib, "pack_frame",
                        lambda *a, mode, **kw: seen.append(mode)
                        or make_frame(a[0], None, a[1], kw["capacity"]))
    monkeypatch.setattr(stlib.fablib, "fabric_route_step",
                        lambda state, frames, plan, **kw: (
                            frames, stlib.fablib.ExchangeDrops(
                                *(jnp.zeros(frames.valid.shape[0],
                                            jnp.int32),) * 4)))
    cfg = netlib.NetworkConfig(n_chips=2, capacity=8, chip=SMALL)
    params = netlib.init_feedforward(KEY, cfg)
    jax.eval_shape(lambda p, s, d: stlib.run_stream(
        p, s, d, cfg, use_fused=use_fused), params,
        netlib.init_state(cfg, 1),
        jnp.zeros((2, 2, 1, SMALL.n_rows)))
    assert seen and set(seen) == {expect}
