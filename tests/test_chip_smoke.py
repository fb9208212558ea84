"""``chip_smoke.py`` on the CPU: its phases with the Pallas kernels in
interpret mode against the unfused oracle, at a reduced chip size, so the
script's comparison logic is guarded without the chip."""

import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.kernels.spike_router import spike_router as sr  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.snn import chip as chiplib  # noqa: E402

SMALL = chiplib.ChipConfig(n_neurons=64, n_rows=32)
# Dense enough stimulus that a 32-row chip fires and the fabric drops.
OCC = 0.3


def test_engine_phase_matches_oracle():
    r = chip_smoke.engine_phase(chip=SMALL, mode="interpret", occupancy=OCC,
                                n_sessions=5, slots=3, window=4,
                                min_steps=6, max_steps=12)
    assert r["mismatches"] == []
    assert r["mode"] == "interpret" and chip_smoke.phase_ok(r)
    assert r["sessions"] == 5 and r["spikes"] > 0
    assert r["delivered_events"] > 0 and r["drops"]["uplink_dropped"] > 0


def test_stream_phase_full_backplane_matches_oracle():
    r = chip_smoke.stream_phase("FULL_BACKPLANE", False, chip=SMALL,
                                mode="interpret", occupancy=OCC, steps=8,
                                batch=2)
    assert r["mismatches"] == [] and chip_smoke.phase_ok(r)
    assert r["spikes"] > 0


def test_stream_phase_reports_a_kernel_fault(monkeypatch):
    """A kernel that adds one nanosecond of queueing is caught on the
    latency lane, and only there."""
    base = sr._dest_queue_ns
    monkeypatch.setattr(sr, "_dest_queue_ns",
                        lambda capacity, queue: base(capacity, queue) + 1)
    jax.clear_caches()
    try:
        r = chip_smoke.stream_phase("EXT_4CASE_96CHIP", True, chip=SMALL,
                                    mode="interpret", occupancy=OCC,
                                    steps=6, batch=2)
    finally:
        jax.clear_caches()
    assert r["mismatches"] == ["EXT_4CASE_96CHIP: latency_ns"]
    assert not chip_smoke.phase_ok(r)


@pytest.mark.parametrize("result, ok", [
    ({"mismatches": [], "mode": "pallas", "kernels_in_program": True}, True),
    ({"mismatches": [], "mode": "pallas", "kernels_in_program": False},
     False),
    ({"mismatches": [], "mode": "interpret", "kernels_in_program": False},
     True),
    ({"mismatches": ["x"], "mode": "pallas", "kernels_in_program": True},
     False),
])
def test_phase_ok(result, ok):
    assert chip_smoke.phase_ok(result) is ok


def test_main_refuses_without_a_tpu(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main() == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_compile_cache_directory(monkeypatch, tmp_path):
    """The environment's directory is left to JAX; otherwise the cache is
    the fixed directory inside the checkout."""
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == saved
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        where = compile_cache.enable_compile_cache()
        assert where == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == where
        assert compile_cache.CHECKOUT_CACHE_DIR.parent == pathlib.Path(
            chip_smoke.__file__).resolve().parent
    finally:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", saved)
        compilation_cache.reset_cache()
