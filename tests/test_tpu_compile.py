"""Ahead-of-time compiles for one TPU v5e chip, without the chip.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology; it refuses what the chip would refuse (block shapes
off the (8, 128) tiling, lowerings Mosaic lacks, more VMEM than a kernel
may use).  These tests compile the spike-router kernels at the shapes the
main path feeds them and the engine's window program at the published chip
size, and check that the Pallas kernels are in the compiled programs
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.analysis import scenarios
from repro.core.latency import timed_wire
from repro.core.routing import FWD_TABLE_SIZE, REV_TABLE_SIZE
from repro.kernels.spike_router import spike_router as sr
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import stream as stlib

# Kernel shapes do not depend on the synapse array; a small chip keeps the
# traced stream programs cheap.
SMALL_CHIP = chiplib.ChipConfig(n_neurons=64, n_rows=32)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the persistent
    # cache without the chip; keep them out of it.
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def pallas_mode(monkeypatch):
    """Steer the main path to the compiled Pallas kernels, as on a TPU."""
    monkeypatch.setattr(repro.kernels, "default_mode", lambda: "pallas")


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *args) -> str:
    return jax.jit(fn).lower(*_on(sharding, args)).compile().as_text()


@pytest.mark.parametrize("timed", [False, True])
@pytest.mark.parametrize("scenario", [c[0] for c in scenarios.CASES])
def test_run_stream_kernels_compile(one_chip, pallas_mode, scenario, timed):
    """The exchange kernels inside ``run_stream`` at each catalogue plan's
    shapes (8 batch rows, vmapped), timed and untimed."""
    cfg, params, plan = scenarios.engine_network(scenario, chip=SMALL_CHIP)
    run = functools.partial(stlib.run_stream, cfg=cfg, fabric=plan,
                            timed=timed)
    text = _compile(lambda p, s, d: run(p, s, d), one_chip, params,
                    netlib.init_state(cfg, 8),
                    jnp.zeros((4, cfg.n_chips, 8, cfg.chip.n_rows)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("timed", [False, True])
@pytest.mark.parametrize("wire16", [False, True])
@pytest.mark.parametrize("scenario", [c[0] for c in scenarios.CASES])
def test_merge_pack_compiles(one_chip, scenario, wire16, timed):
    """``merge_pack_fwd`` on one plan's full merge stream (every level's
    segments) with per-stream rev LUTs."""
    cfg, _, plan = scenarios.engine_network(scenario, chip=SMALL_CHIP)
    n = plan.n_nodes
    width = sum(sum(level) for level in plan.merge_layout(cfg.capacity))
    labels = jnp.zeros((n, width), jnp.int16 if wire16 else jnp.int32)
    valid = jnp.zeros((n, width), jnp.int32)
    rev = jnp.zeros((n, REV_TABLE_SIZE), jnp.int32)
    if timed:
        queue = timed_wire(cfg.latency).queue
        fn = functools.partial(sr.merge_pack_fwd, capacity=plan.capacity,
                               queue=queue)
        text = _compile(lambda l, v, r, t: fn(l, v, r, times=t), one_chip,
                        labels, valid, rev, valid)
    else:
        fn = functools.partial(sr.merge_pack_fwd, capacity=plan.capacity)
        text = _compile(fn, one_chip, labels, valid, rev)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("scenario", [c[0] for c in scenarios.CASES])
def test_exchange_kernels_compile(one_chip, scenario):
    """``exchange_fwd`` / ``exchange_stream_fwd`` (single-level rounds over a
    plan's leaves) and the egress-only ``spike_router_fwd``."""
    cfg, _, plan = scenarios.engine_network(scenario, chip=SMALL_CHIP)
    n, cap_in = plan.n_nodes, cfg.capacity
    labels = jnp.zeros((n, cap_in), jnp.int32)
    fwd = jnp.zeros((n, FWD_TABLE_SIZE), jnp.int32)
    rev = jnp.zeros((n, REV_TABLE_SIZE), jnp.int32)
    en = jnp.ones((n, n), jnp.int32)
    kw = dict(capacity=plan.capacity)
    texts = [
        _compile(functools.partial(sr.exchange_fwd, **kw), one_chip,
                 labels, labels, fwd, rev, en),
        _compile(functools.partial(sr.exchange_stream_fwd, **kw), one_chip,
                 jnp.stack([labels] * 4), jnp.stack([labels] * 4), fwd, rev,
                 en),
        _compile(functools.partial(sr.spike_router_fwd, capacity=cap_in),
                 one_chip, jnp.zeros((n, chiplib.N_NEURONS), jnp.int32),
                 jnp.zeros((n, chiplib.N_NEURONS), jnp.int32), fwd[0]),
    ]
    assert all("tpu_custom_call" in t for t in texts)


@pytest.mark.parametrize("rows, capacity", [(480, 128), (1536, 96)])
def test_egress_pack_compiles(one_chip, rows, capacity):
    """The egress pack at the benchmark cells' shapes: batch x chips rows
    of the published chip's 512 neurons, packed to the ingress capacity."""
    from repro.kernels.spike_router.ops import pack_frame

    fn = functools.partial(pack_frame, capacity=capacity, mode="pallas")
    text = _compile(fn, one_chip,
                    jnp.zeros((rows, chiplib.N_NEURONS), jnp.int32),
                    jnp.zeros((rows, chiplib.N_NEURONS), jnp.bool_))
    assert "tpu_custom_call" in text


def test_engine_window_program_compiles(one_chip, pallas_mode):
    """The engine's window program at the published 256x512 chip on the
    96-chip extension fabric: timed, per-slot STDP, 8 slots — the program
    ``chip_smoke.py`` runs — fits the chip and carries the kernels."""
    from repro.runtime.engine import EmulationEngine
    from repro.snn.plasticity import STDPConfig

    cfg, params, plan = scenarios.engine_network("EXT_4CASE_96CHIP")
    eng = EmulationEngine(params, cfg, slots=8, max_steps=32, window=8,
                          plan=plan, timed=True, plasticity=STDPConfig())
    compiled = eng.window_fn.lower(
        *_on(one_chip, eng.window_args())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2 ** 30          # one v5e chip's HBM
