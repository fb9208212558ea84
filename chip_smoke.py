"""Smoke run of the emulation engine's main path on one TPU chip.

    python chip_smoke.py

Run from the root of a checkout (it imports ``src/``).  One process drives
the chip; nothing here starts another.  It fails, printing no result line,
when JAX finds no TPU.

Phase A, the engine: ``EmulationEngine`` on ``EXT_4CASE_96CHIP`` (96 chips
at the published 256x512 synapse array, 8 slots, 8-step windows, timed wire,
per-slot STDP) serves 12 tenant sessions of 16-32 steps of Poisson stimulus
at the paper-typical occupancy, through ``submit``/``step``/``collect``.
Each session is compared bit-exactly against an independent batch-1
``run_stream`` with ``use_fused=False`` (the unfused pure-JAX composition)
on the same device: spikes, the four drop fields, latency statistics and the
final plasticity state.

Phase B, the other plans: ``run_stream`` on ``FULL_BACKPLANE`` untimed (the
caller of the single-round exchange kernel) and on ``PROJECTED_120CHIP``
timed, each compared bit-exactly on every output against
``use_fused=False``.

Every phase checks that its compiled program contains the Pallas kernels
(``tpu_custom_call``), i.e. that the kernels ran and not the oracle.  The
times printed are host-clock times of a smoke run, not a benchmark.  The
last line of standard output is the JSON result.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import math
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.analysis import scenarios  # noqa: E402

ENGINE_SCENARIO = "EXT_4CASE_96CHIP"
STREAM_PHASES = (("FULL_BACKPLANE", False), ("PROJECTED_120CHIP", True))
DROP_FIELDS = ("dropped", "uplink_dropped", "unroutable", "rerouted")


@contextlib.contextmanager
def kernel_mode(mode: str | None):
    """Run the exchange kernels in ``mode`` ("pallas" / "interpret" /
    "jax"); ``None`` keeps the automatic choice (Pallas on a TPU)."""
    import repro.kernels as kernels

    saved = kernels.default_mode
    if mode is not None:
        kernels.default_mode = lambda: mode
    try:
        yield kernels.default_mode()
    finally:
        kernels.default_mode = saved


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def _same_stats(a: dict | None, b: dict | None) -> bool:
    if a is None or b is None:
        return a is b
    return a.keys() == b.keys() and all(
        a[k] == b[k] or (math.isnan(a[k]) and math.isnan(b[k])) for k in a)


def engine_phase(*, chip=None, slots: int = 8, window: int = 8,
                 n_sessions: int = 12, min_steps: int = 16,
                 max_steps: int = 32, seed: int = 0,
                 occupancy: float = scenarios.OCC_HEADLINE,
                 mode: str | None = None) -> dict:
    """Phase A: drain ``n_sessions`` through the engine and compare each
    with an independent batch-1 unfused ``run_stream``."""
    import jax

    from repro.runtime.engine import EmulationEngine
    from repro.snn import network as netlib
    from repro.snn import stream as stlib
    from repro.snn.plasticity import STDPConfig

    cfg, params, plan = scenarios.engine_network(ENGINE_SCENARIO, chip=chip,
                                                 seed=seed)
    stdp = STDPConfig()
    rng = np.random.default_rng(seed)
    lengths = rng.integers(min_steps, max_steps + 1, size=n_sessions)
    stims = [(rng.uniform(size=(int(t), cfg.chip.n_rows)) < occupancy
              ).astype(np.float32) for t in lengths]

    with kernel_mode(mode) as used:
        eng = EmulationEngine(params, cfg, slots=slots, max_steps=max_steps,
                              window=window, plan=plan, timed=True,
                              plasticity=stdp)
        t0 = time.perf_counter()
        program = eng.window_fn.lower(*eng.window_args()).compile().as_text()
        eng.warm()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sids = [eng.submit(s) for s in stims]
        eng.drain()
        results = [eng.collect(sid) for sid in sids]
        run_s = time.perf_counter() - t0

    # The reference: one batch-1 program at the longest session length; a
    # shorter session masks its tail steps (no spikes, frozen plasticity).
    @jax.jit
    def reference(p, drives, mask):
        return stlib.run_stream(
            p, netlib.init_state(cfg, 1), drives, cfg, fabric=plan,
            timed=True, use_fused=False, plasticity=stdp,
            plasticity_state=netlib.init_slot_plasticity(p, 1),
            slot_mask=mask)

    mismatches = []
    for r, stim in zip(results, stims):
        t = stim.shape[0]
        drives = np.zeros((max_steps, cfg.n_chips, 1, cfg.chip.n_rows),
                          np.float32)
        drives[:t, eng.stim_chips[0], 0] = stim
        ref = reference(params, drives, (np.arange(max_steps) < t)[:, None])
        tag = f"session {r.session_id}"
        if r.steps != t or not np.array_equal(
                r.spikes, np.asarray(ref.spikes)[:t, :, 0]):
            mismatches.append(f"{tag}: spikes")
        for f in DROP_FIELDS:
            if getattr(r, f) != int(np.asarray(getattr(ref, f)).sum()):
                mismatches.append(f"{tag}: {f}")
        if not _same_stats(r.latency, stlib.masked_latency_stats(
                ref.latency_ns, ref.latency_valid, strict=False)):
            mismatches.append(f"{tag}: latency")
        ref_plast = jax.tree.map(lambda a: np.asarray(a)[:, 0],
                                 ref.plasticity)
        if not all(np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(r.plasticity), jax.tree.leaves(ref_plast))):
            mismatches.append(f"{tag}: plasticity")

    return {
        "phase": "A engine", "scenario": ENGINE_SCENARIO, "mode": used,
        "compile_s": compile_s, "run_s": run_s,
        "kernels_in_program": "tpu_custom_call" in program,
        "sessions": len(results), "steps": int(lengths.sum()),
        "spikes": sum(r.spike_count for r in results),
        "drops": {f: sum(getattr(r, f) for r in results)
                  for f in DROP_FIELDS},
        "delivered_events": sum(r.latency["count"] for r in results),
        "spike_digest": _digest(*(r.spikes for r in results)),
        "mismatches": mismatches,
    }


def stream_phase(name: str, timed: bool, *, chip=None, batch: int = 4,
                 steps: int = 32, seed: int = 0,
                 occupancy: float = scenarios.OCC_HEADLINE,
                 mode: str | None = None) -> dict:
    """Phase B: one ``run_stream`` through the kernels vs ``use_fused=False``,
    every output compared."""
    import jax

    from repro.snn import network as netlib
    from repro.snn import stream as stlib

    cfg, params, plan = scenarios.engine_network(name, chip=chip, seed=seed)
    rng = np.random.default_rng(seed)
    drives = (rng.uniform(size=(steps, cfg.n_chips, batch, cfg.chip.n_rows))
              < occupancy).astype(np.float32)

    def run(p, d, *, fused):
        return stlib.run_stream(p, netlib.init_state(cfg, batch), d, cfg,
                                fabric=plan, timed=timed, use_fused=fused)

    with kernel_mode(mode) as used:
        t0 = time.perf_counter()
        compiled = jax.jit(functools.partial(run, fused=True)).lower(
            params, drives).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(params, drives))
        run_s = time.perf_counter() - t0
    ref = jax.jit(functools.partial(run, fused=False))(params, drives)

    mismatches = [
        f"{name}: {field}" for field in out._fields
        if not all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(getattr(out, field)),
            jax.tree.leaves(getattr(ref, field))))]
    return {
        "phase": "B stream", "scenario": name, "timed": timed, "mode": used,
        "compile_s": compile_s, "run_s": run_s,
        "kernels_in_program": "tpu_custom_call" in compiled.as_text(),
        "steps": steps, "batch": batch,
        "spikes": int(np.asarray(out.spikes).sum()),
        "drops": {f: int(np.asarray(getattr(out, f)).sum())
                  for f in DROP_FIELDS},
        "delivered_events": int(np.asarray(out.latency_valid).sum()),
        "spike_digest": _digest(out.spikes),
        "mismatches": mismatches,
    }


def phase_ok(result: dict) -> bool:
    """A phase passes when it matched its reference everywhere and, with the
    compiled Pallas kernels selected, the program really contains them."""
    return not result["mismatches"] and (
        result["mode"] != "pallas" or result["kernels_in_program"])


def main() -> int:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"jax {jax.__version__}; device {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    print("times: host clock of one smoke run, not a benchmark", flush=True)

    ok = True
    phases = [engine_phase] + [functools.partial(stream_phase, n, t)
                               for n, t in STREAM_PHASES]
    for phase in phases:
        try:
            result = phase()
        except Exception as e:  # noqa: BLE001 — report and fail the run
            print(f"phase {getattr(phase, '__name__', phase)} raised "
                  f"{type(e).__name__}: {e}", flush=True)
            ok = False
            continue
        ok &= phase_ok(result)
        print(json.dumps(result), flush=True)

    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
