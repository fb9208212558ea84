"""The measurement the programs carry: named scopes in the compiled window
and stream programs, and the engine's ``engine.*`` host spans with their
counters under the profiler.

A 3-level fabric of 8 small chips keeps the programs cheap; the engine runs
per-slot plasticity, so every scope of the window program is present.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fabric as fablib
from repro.core.aggregator import identity_router
from repro.runtime.engine import EmulationEngine
from repro.snn import chip as chiplib
from repro.snn import network as netlib
from repro.snn import stream as stlib
from repro.snn.plasticity import STDPConfig

LEVELS = 3
SLOTS, WINDOW = 3, 4
LENGTHS = (9, 4, 12, 6, 5)
STREAM_SCOPES = {"chip", "egress", "fabric", "ingress"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _network():
    chip = chiplib.ChipConfig(n_neurons=24, n_rows=12)
    cfg = netlib.NetworkConfig(n_chips=2 ** LEVELS, capacity=16, chip=chip)
    params = netlib.init_feedforward(jax.random.PRNGKey(5), cfg)._replace(
        router=identity_router(cfg.n_chips))
    plan = fablib.compile_fabric(fablib.FabricSpec(
        levels=tuple(fablib.LevelSpec(2) for _ in range(LEVELS)),
        capacity=cfg.capacity))
    return cfg, params, plan


def _engine(**kw):
    cfg, params, plan = _network()
    return cfg, EmulationEngine(params, cfg, slots=SLOTS, max_steps=16,
                                plan=plan, window=WINDOW,
                                plasticity=STDPConfig(), **kw)


def _stims(cfg):
    rng = np.random.default_rng(11)
    return [(rng.uniform(size=(n, cfg.chip.n_rows)) < 0.4).astype(np.float32)
            for n in LENGTHS]


def _name_stacks(hlo_text: str) -> list[list[str]]:
    """Every op's name stack, transform wrappers (``vmap(...)``) removed."""
    out = []
    for path in set(re.findall(r'op_name="([^"]*)"', hlo_text)):
        comps = []
        for c in path.split("/"):
            while (m := _WRAPPED.match(c)):
                c = m.group(1)
            comps.append(c)
        out.append(comps)
    return out


def _scopes(hlo_text: str) -> tuple[set, set]:
    stacks = _name_stacks(hlo_text)
    top = {c for s in stacks for c in s
           if c in STREAM_SCOPES | {"plasticity", "slots"}}
    fabric = {s[k + 1] for s in stacks for k in range(len(s) - 1)
              if s[k] == "fabric"}
    return top, fabric


def test_engine_window_program_names_its_scopes():
    _, eng = _engine()
    text = eng.window_fn.lower(*eng.window_args()).compile().as_text()
    top, fabric = _scopes(text)
    assert top == STREAM_SCOPES | {"plasticity", "slots"}
    assert {f"level{i}" for i in range(LEVELS)} | {"leaf", "merge"} <= fabric


def test_stream_program_names_its_scopes():
    cfg, params, plan = _network()
    drives = jnp.zeros((6, cfg.n_chips, 2, cfg.chip.n_rows), jnp.float32)
    fn = jax.jit(lambda p, s, d: stlib.run_stream(p, s, d, cfg, fabric=plan,
                                                  timed=True))
    text = fn.lower(params, netlib.init_state(cfg, 2),
                    drives).compile().as_text()
    top, fabric = _scopes(text)
    assert top == STREAM_SCOPES                  # no plasticity, no slots
    assert {f"level{i}" for i in range(LEVELS)} | {"leaf", "merge"} <= fabric


def _trace_engine(tmp_path, **kw):
    """Drive a small engine under the profiler, counting the live
    slot-steps from outside as the benchmark's driving path does: sessions
    are admitted in submission order, so the first ``active`` uncollected
    ones hold the slots."""
    from jax.profiler import ProfileData

    cfg, eng = _engine(**kw)
    eng.warm()
    stims = _stims(cfg)
    with jax.profiler.trace(str(tmp_path)):
        pending = [[eng.submit(s), s.shape[0]] for s in stims]
        live, steps, finished = 0, 0, 0
        while eng.active or eng.queued:
            for item in pending[:eng.active]:
                live += min(WINDOW, item[1])
                item[1] = max(0, item[1] - WINDOW)
            finished += eng.step()
            steps += 1
            for sid in eng.done:
                eng.collect(sid)
                pending[:] = [p for p in pending if p[0] != sid]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    return cfg, eng, spans, {"live": live, "steps": steps,
                             "finished": finished}


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_engine_spans_and_counters(tmp_path):
    cfg, eng, spans, seen = _trace_engine(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    steps = by["engine.step"]
    assert len(steps) == seen["steps"]
    for name in ("engine.upload", "engine.wait", "engine.account"):
        assert len(by[name]) == seen["steps"]
        assert all(any(_inside(sp, st) for st in steps) for sp in by[name])
    assert sum(sp[3]["live"] for sp in steps) == seen["live"]
    assert all(sp[3]["slot_steps"] == SLOTS * WINDOW for sp in steps)
    assert sum(sp[3]["finished"] for sp in steps) == seen["finished"]
    assert seen["finished"] == len(LENGTHS)
    # One finalize per session, one admission per session.
    assert sorted(sp[3]["session"] for sp in by["engine.finalize"]) == \
        list(range(len(LENGTHS)))
    assert sorted(sp[3]["session"] for sp in by["engine.admit"]) == \
        list(range(len(LENGTHS)))
    assert all(sp[3]["queued_ms"] >= 0 for sp in by["engine.admit"])
    # Readbacks: the window's raster and drop counters inside each account
    # span, the plasticity row inside each finalize span, with the bytes
    # of the arrays copied.
    n, c, r = cfg.n_chips, cfg.chip.n_neurons, cfg.chip.n_rows
    raster = WINDOW * n * SLOTS * c * 4 + 4 * WINDOW * n * SLOTS * 4
    row = n * (r + c + r * c) * 4
    stim = SLOTS * (16 + WINDOW) * 1 * r * 4
    upload = stim + SLOTS * 4 + WINDOW * SLOTS + SLOTS
    assert all(sp[3]["bytes"] == upload for sp in by["engine.upload"])
    reads = {"raster": [], "plasticity": []}
    for sp in by["engine.readback"]:
        reads[sp[3]["what"]].append(sp)
    assert len(reads["raster"]) == seen["steps"]
    assert len(reads["plasticity"]) == len(LENGTHS)
    assert all(sp[3]["bytes"] == raster for sp in reads["raster"])
    assert all(sp[3]["bytes"] == row for sp in reads["plasticity"])
    assert all(any(_inside(sp, a) for a in by["engine.account"])
               for sp in reads["raster"])
    assert all(any(_inside(sp, f) for f in by["engine.finalize"])
               for sp in reads["plasticity"])


def test_readback_bytes_match_the_arrays_copied():
    """The raster readback's ``bytes`` are the ``nbytes`` of the payload
    arrays the engine copies, taken from the window program's outputs."""
    cfg, eng = _engine()
    out = jax.eval_shape(eng.window_fn, *eng.window_args())[2]
    copied = [out.spikes, out.dropped, out.uplink_dropped, out.unroutable,
              out.rerouted]
    n, c = cfg.n_chips, cfg.chip.n_neurons
    assert sum(np.dtype(a.dtype).itemsize * int(np.prod(a.shape))
               for a in copied) == (WINDOW * n * SLOTS * c * 4
                                    + 4 * WINDOW * n * SLOTS * 4)


def test_spans_leave_results_unchanged(tmp_path):
    """The same sessions under the profiler and without it give the same
    spikes, drops and plasticity rows."""
    cfg, eng = _engine()
    stims = _stims(cfg)
    ids = [eng.submit(s) for s in stims]
    plain = eng.drain()
    _, eng2 = _engine()
    with jax.profiler.trace(str(tmp_path)):
        ids2 = [eng2.submit(s) for s in stims]
        traced = eng2.drain()
    for a, b in zip(ids, ids2):
        ra, rb = plain[a], traced[b]
        assert np.array_equal(ra.spikes, rb.spikes)
        assert (ra.dropped, ra.uplink_dropped, ra.unroutable,
                ra.rerouted) == (rb.dropped, rb.uplink_dropped,
                                 rb.unroutable, rb.rerouted)
        for x, y in zip(jax.tree.leaves(ra.plasticity),
                        jax.tree.leaves(rb.plasticity)):
            assert np.array_equal(x, y)


@pytest.mark.parametrize("keep_spikes", [True, False])
def test_accounting_only_engine_reads_back_counts(tmp_path, keep_spikes):
    """Without kept spikes the window's readback is the per-slot counts."""
    _, _, spans, seen = _trace_engine(tmp_path, keep_spikes=keep_spikes)
    whats = {sp[3]["what"] for sp in spans if sp[0] == "engine.readback"}
    assert whats == ({"raster", "plasticity"} if keep_spikes
                     else {"counts", "plasticity"})
    assert sum(sp[3]["live"] for sp in spans
               if sp[0] == "engine.step") == seen["live"]
