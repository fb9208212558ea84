"""The benchmark as data: every cell resolves its files by name, the traffic
is a function of the seed, the peaks and the semantic work match the hand
counts, and ``bench/run.py`` refuses to run without a TPU."""

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import peaks, reference, spec, system, traffic  # noqa: E402
from bench.harness import work  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    return env


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_its_files_by_name(workload):
    cell = spec.resolve(BENCH, workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    assert spec.config_path(w["config"]).is_file()
    assert spec.traffic_path(w["traffic"]).is_file()
    assert cell.cfg["name"] == w["config"]
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))
    for m in cell.per_layer:
        assert m["moves"] in reported
    assert spec.driver_path(cell.traffic["path"]).is_file()
    runner = spec.driver(cell.traffic["path"])(cell.cfg, cell.traffic, 3)
    for attr in ("unit", "setup", "window", "release", "check"):
        assert hasattr(runner, attr)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_has_its_tiny_copy(workload):
    """The CPU tests of ``correct`` find a cell's small copy by name:
    ``tests/data/tiny_<config>.json`` and ``tests/data/tiny_<traffic>.json``,
    of the same driving path and keys as the cell's own files."""
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    data = ROOT / "bench" / "tests" / "data"
    cfg = json.loads((data / f"tiny_{w['config']}.json").read_text())
    mix = json.loads((data / f"tiny_{w['traffic']}.json").read_text())
    cell = spec.resolve(BENCH, workload)
    assert mix["path"] == cell.traffic["path"]
    assert set(cfg) == set(cell.cfg)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_lists_every_reduction(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["reduced"] == entry["reduced"] == []
    for per_slot in (True, False):
        assert reference.contraction_mode(cfg, per_slot=per_slot) in (
            "float32", "bfloat16_operands")
    assert set(cfg["limits"]) >= {"spike_gap", "fabric_mismatches"}


def _engine_mix():
    return json.loads(spec.traffic_path("engine_plastic").read_text())


def test_session_lengths_same_work_for_every_seed():
    mix = _engine_mix()
    a, b = (traffic.session_lengths(mix, s) for s in (2**31 + 7, 12))
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert np.array_equal(a, traffic.session_lengths(mix, 2**31 + 7))
    assert a.min() >= 16 and a.max() <= 64


def test_session_stimulus_deterministic_and_seeded():
    mix = _engine_mix()
    cfg = json.loads(spec.config_path("ext4case_96chip").read_text())
    s1 = traffic.session_stimulus(mix, cfg, 2**33 + 1, 5, 40)
    assert s1.shape == (40, 1, 256) and s1.dtype == np.float32
    assert np.array_equal(s1, traffic.session_stimulus(mix, cfg, 2**33 + 1,
                                                       5, 40))
    assert not np.array_equal(s1, traffic.session_stimulus(mix, cfg, 3, 5, 40))
    assert 0.03 < s1.mean() < 0.07


@pytest.mark.parametrize("law,lo,hi", [("fixed", 32, 32), ("uniform", 8, 40),
                                       ("log_uniform", 16, 64)])
def test_session_length_laws(law, lo, hi):
    mix = dict(_engine_mix(), session_steps={"law": law, "min": lo,
                                             "max": hi, "strata": 16})
    a, b = (traffic.session_lengths(mix, s) for s in (2**32 + 3, 5))
    assert sorted(a) == sorted(b) and len(a) == 16
    assert a.min() >= lo and a.max() <= hi
    assert np.array_equal(a, traffic.session_lengths(mix, 2**32 + 3))
    if law == "log_uniform":
        assert np.median(a) < (lo + hi) / 2
    if law != "fixed":
        assert not np.array_equal(a, b)


def test_zipf_skew_keeps_the_total_rate():
    mix = dict(_engine_mix(), stimulus={"chips": "all", "rate": 0.02,
                                        "skew": {"law": "zipf", "s": 1.1}})
    cfg = json.loads(spec.config_path("projected_120chip").read_text())
    rates = traffic.chip_rates(mix, cfg)
    assert rates.shape == (120,) and np.all(np.diff(rates) <= 0)
    assert rates.mean() == pytest.approx(0.02)
    assert rates[0] > 10 * rates[-1]
    flat = traffic.chip_rates(dict(mix, stimulus={"chips": [0, 5],
                                                  "rate": 0.05}), cfg)
    assert np.array_equal(flat, [0.05, 0.05])


def test_open_arrival_gaps():
    mix = dict(_engine_mix(), arrivals={"kind": "open", "rate_per_s": 20.0,
                                        "burst": 4, "strata": 50})
    a, b = (traffic.arrival_gaps(mix, s) for s in (2**31 + 11, 2))
    assert a.shape == (200,) and sorted(a) == sorted(b)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, traffic.arrival_gaps(mix, 2**31 + 11))
    assert (a == 0).sum() == 150                   # inside the bursts
    assert a.sum() / len(a) == pytest.approx(1 / 20.0, rel=0.05)


def test_every_chip_owns_a_block_of_wire_labels():
    """120 chips of 512 neurons: chip c's neuron j leaves as wire label
    c * 256 + j % 256 and comes back as c << 9 | j % 256, so no chip is cut
    off at its forward LUT (the program's own table lookups read them)."""
    from repro.core import routing

    import jax

    cfg = json.loads(spec.config_path("projected_120chip").read_text())
    _, _, _, row_of_label, fwd, rev = system.make_arrays(cfg, 2**31 + 1)
    chips = np.arange(120)[:, None]
    labels = (chips << 9) + np.arange(512)[None]
    wire, en = (np.asarray(a) for a in jax.vmap(routing.lookup_fwd)(
        fwd, labels))
    assert en.all()
    assert np.array_equal(wire, chips * 256 + np.arange(512)[None] % 256)
    back, ok = (np.asarray(a) for a in jax.vmap(routing.lookup_rev)(
        rev, wire))
    assert ok.all()
    assert np.array_equal(back, (chips << 9) + np.arange(512)[None] % 256)
    # Another chip's label is disabled at a chip's forward LUT.
    _, other = routing.lookup_fwd(fwd[3], labels[4])
    assert not np.asarray(other).any()
    # Chip 101 takes chip 100's neuron 300 on row 44.
    assert int(row_of_label[101][back[100, 300]]) == 44
    # The reference delivers the same event.
    spikes = np.zeros((1, 120, 512), bool)
    spikes[0, 100, 300] = True
    out = reference.FabricRef(cfg).route(spikes)
    assert out.drive[0, 101, 44] == 1.0 and out.drive.sum() == 1.0


def test_drive_pool_deterministic_and_seeded():
    mix = json.loads(spec.traffic_path("stream_timed").read_text())
    mix = dict(mix, steps_per_call=3, batch=2,
               stimulus=dict(mix["stimulus"], pool=2))
    cfg = json.loads(spec.config_path("projected_120chip").read_text())
    a = np.asarray(traffic.drive_pool(mix, cfg, 2**31 + 99))
    assert a.shape == (2, 3, 120, 2, 256)
    assert np.array_equal(a, np.asarray(traffic.drive_pool(mix, cfg,
                                                           2**31 + 99)))
    assert not np.array_equal(a, np.asarray(traffic.drive_pool(mix, cfg, 1)))


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_semantic_work_hand_counts():
    ext = json.loads(spec.config_path("ext4case_96chip").read_text())
    proj = json.loads(spec.config_path("projected_120chip").read_text())
    # Per-slot weights: 2 x 16 slots x 96 chips x 256 x 512 synapses x 1 B.
    w = work.step_work(ext, live_rows=16, plastic=True, timed=False)
    assert w["parts"]["weights"] == 402_653_184
    assert w["int8_ops"] == 2 * 16 * 96 * 131_072
    # Shared weights are read once: 120 chips x 131,072 B.
    w = work.step_work(proj, live_rows=4, plastic=False, timed=True)
    assert w["parts"]["weights"] == 15_728_640
    assert w["parts"]["neuron_state"] == 2 * 4 * 120 * 512 * 16
    assert w["parts"]["delay_line"] == 2 * 4 * 120 * 256 * 4
    # Frame slots: egress, leaf lane, every level's part and uplink, ingress.
    assert work.frame_slots(ext) == (9216 + 768 + 9216 + 240 + 5760 + 232
                                     + 22272 + 9216)
    assert work.frame_slots(proj) == 15360 + 960 + 11520 + 400 + 48000 + 15360
    assert w["parts"]["event_frames"] == 2 * 4 * 91_600 * 6
    share, bound = work.roofline(819e9, 0.0, 2.0, peaks.peaks("TPU v5 lite"))
    assert share == pytest.approx(50.0) and bound == "hbm"


def test_run_exits_nonzero_without_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "no TPU" in proc.stderr


def test_nothing_loads_libtpu_at_import():
    code = (
        "import sys\n"
        "import bench.run, bench.harness.driving, bench.harness.check\n"
        "import bench.harness.trace, bench.harness.readers\n"
        "from bench.harness import spec\n"
        "for p in sorted(spec.BENCH.glob('metrics/*.py')):\n"
        "    spec.reader(p.stem)\n"
        "for p in sorted(spec.BENCH.glob('drivers/*.py')):\n"
        "    spec.driver(p.stem)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "assert not [m for m in sys.modules if 'libtpu' in m]\n"
        "print('ok')\n")
    env = _env()
    env.pop("JAX_PLATFORMS")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


def test_compile_counter_sees_a_compilation_only_while_open():
    import jax

    from bench import run

    with run.CompileCounter() as inside:
        jax.jit(lambda x: x * 5 + 2)(1.0)
    jax.jit(lambda x: x * 7 + 3)(1.0)
    assert inside.count == 1
