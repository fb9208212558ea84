"""``correct`` on a small copy of each cell, on the CPU.

A sound run holds every limit.  The control (the reference at the
precision below the stated one, in the program's place) and each fault the
cells can have (a step that hands its state back unchanged, half of the
batch left out, the exchange between chips left out, a spike altered where
it is produced) fail at least one.  The harness's look for a chip is
skipped; the rest of a run is driven as ``bench/run.py`` drives it.
"""

import contextlib
import json
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run as runmod  # noqa: E402
from bench.harness import check as checklib  # noqa: E402
from bench.harness import reference as ref  # noqa: E402
from bench.harness import spec  # noqa: E402

DATA = pathlib.Path(__file__).with_name("data")
# Each cell's small copy is found by name: ``data/tiny_<config>.json`` and
# ``data/tiny_<traffic>.json``.
CELLS = {w["name"]: (f"tiny_{w['config']}", f"tiny_{w['traffic']}")
         for w in spec.load_benchmark(ROOT)["workloads"]}
SEED = 2**31 + 4242


def tiny_cell(workload, **mix):
    cell = spec.resolve(spec.load_benchmark(ROOT), workload)
    cfg, name = CELLS[workload]
    cell.cfg = json.loads((DATA / f"{cfg}.json").read_text())
    cell.traffic = json.loads((DATA / f"{name}.json").read_text())
    cell.traffic.update(mix)
    # The program computes in float32 on the CPU.
    cell.cfg["precision"].update(per_slot_contraction="float32",
                                 shared_contraction="float32")
    return cell


def run(workload, control_mode=None, seconds=0.3, **mix):
    return runmod.run_cell(tiny_cell(workload, **mix), SEED, seconds, False,
                           jax.devices(), t_start=time.perf_counter(),
                           control_mode=control_mode)


@contextlib.contextmanager
def broken(fault):
    """Break the timed path underneath the harness."""
    from repro.core import fabric
    from repro.core.events import EventFrame
    from repro.snn import stream

    real_stream, real_route = stream.run_stream, fabric.fabric_route_step

    def stale_state(params, state, *a, **k):
        return real_stream(params, state, *a, **k)._replace(state=state)

    def half_batch(*a, **k):
        out = real_stream(*a, **k)
        half = out.spikes.shape[2] // 2
        cut = lambda x: x.at[:, :, half:].set(jnp.zeros_like(x[:, :, half:]))
        return out._replace(spikes=cut(out.spikes), dropped=cut(out.dropped),
                            uplink_dropped=cut(out.uplink_dropped),
                            latency_ns=cut(out.latency_ns),
                            latency_valid=cut(out.latency_valid))

    def altered_spike(*a, **k):
        out = real_stream(*a, **k)
        s = out.spikes
        return out._replace(spikes=s.at[0, 1, :, 0].set(1.0 - s[0, 1, :, 0]))

    def no_exchange(state, frames, plan, **k):
        ingress, drops = real_route(state, frames, plan, **k)
        empty = EventFrame(labels=jnp.zeros_like(ingress.labels),
                           times=jnp.zeros_like(ingress.times),
                           valid=jnp.zeros_like(ingress.valid))
        return empty, drops

    patches = {"stale_state": (stream, "run_stream", stale_state),
               "half_batch": (stream, "run_stream", half_batch),
               "altered_spike": (stream, "run_stream", altered_spike),
               "no_exchange": (fabric, "fabric_route_step", no_exchange)}
    mod, name, fn = patches[fault]
    setattr(mod, name, fn)
    try:
        yield
    finally:
        stream.run_stream, fabric.fabric_route_step = real_stream, real_route


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["readings"]
    assert res["readings"]["checked"] >= 2


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_fails(workload):
    cell = tiny_cell(workload)
    mode = ref.CONTROL_OF[ref.contraction_mode(
        cell.cfg, per_slot=cell.traffic["plastic"])]
    res = run(workload, control_mode=mode)
    assert res["correct"]                      # the program itself is sound
    _, held = checklib.verdict(res["readings"],
                               checklib.limits(cell.cfg, cell.traffic),
                               prefix="control_")
    assert not held, res["readings"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "no_exchange", "altered_spike"])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_fails(workload, fault):
    with broken(fault):
        res = run(workload)
    assert not res["correct"], res["readings"]


def test_last_line_has_the_contract_keys(capsys):
    res = run("projected_120chip.stream_timed")
    runmod.emit(res)
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"chip_steps_per_s", "setup_s"}
    assert out.err.strip().splitlines()[-1].startswith("check ")
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


ENGINE = "ext4case_96chip.engine_plastic"
VARIANTS = {
    "shared_weights": {"plastic": False},
    "open_loop": {"arrivals": {"kind": "open", "rate_per_s": 40.0,
                               "burst": 2, "strata": 8}},
    "no_spikes_kept": {"keep_spikes": False, "limits": {"count_gap": 0.0}},
    "skewed_all_chips": {"stimulus": {"chips": "all", "rate": 0.1,
                                      "skew": {"law": "zipf", "s": 1.2}}},
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_engine_mix_variant_is_correct(variant):
    """Each option a mix can set drives the engine and is compared."""
    res = run(ENGINE, **VARIANTS[variant])
    assert res["correct"], res["readings"]
    assert res["readings"]["checked"] >= 2
    if variant == "no_spikes_kept":
        assert "count_gap" in res["checks"]
        assert "spike_gap" not in res["checks"]
    if variant == "shared_weights":
        assert "plasticity_gap" not in res["checks"]


def test_timed_engine_compares_latency_statistics():
    """A timed session's latency statistics are held against the
    reference's timestamps (the engine compiles them inside the window, so
    only the comparison is asserted here)."""
    res = run(ENGINE, timed=True)
    assert res["readings"]["fabric_mismatches"] == 0, res["readings"]
    assert res["readings"]["spike_gap"] <= 1e-3


@pytest.mark.parametrize("fault", ["altered_spike", "no_exchange"])
def test_fault_fails_without_kept_spikes(fault):
    with broken(fault):
        res = run(ENGINE, **VARIANTS["no_spikes_kept"])
    assert not res["correct"], res["readings"]


def test_a_number_without_a_limit_is_an_error():
    with pytest.raises(KeyError, match="count_gap"):
        run(ENGINE, keep_spikes=False)


def test_traced_run_reports_the_cells_layers(monkeypatch, tmp_path):
    """A traced run reduces the program's scopes and spans after the window
    and reports every per-layer metric of the cell.  The CPU's trace has no
    TPU plane, so the recorded chip trace of the stream program stands in
    for it."""
    import gzip
    import shutil

    from bench.harness import peaks as peakslib
    from bench.harness import trace as tracelib

    recorded = tmp_path / "scoped.xplane.pb"
    with gzip.open(DATA / "small_trace_scoped.xplane.pb.gz", "rb") as src, \
            open(recorded, "wb") as dst:
        shutil.copyfileobj(src, dst)
    v5e = peakslib.peaks("TPU v5 lite")
    monkeypatch.setattr(tracelib, "find_xplane", lambda _: str(recorded))
    monkeypatch.setattr(peakslib, "peaks", lambda _: v5e)
    cell = tiny_cell("projected_120chip.stream_timed")
    res = runmod.run_cell(cell, SEED, 0.3, True, jax.devices(),
                          t_start=time.perf_counter())
    assert res["correct"], res["readings"]
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    assert res["metrics"]["stream.fabric_leaf_ms_per_step"]["value"] > 0
    assert res["device"]["busy_s"] > 0
