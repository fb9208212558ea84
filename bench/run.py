"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It builds the cell's system from its
configuration file and the seed, warms every shape the window uses (set-up),
drives the cell's traffic for ``--seconds`` (with ``--trace 1``: for the
mix's ``trace_seconds`` at most, under the profiler), compares a sample of
what the window produced with the plain reference, and prints one JSON
line as the last line of standard output.  The numbers compared, each
beside its limit, are the last lines of standard error and the last key of
that line.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chips(n: int):
    """The devices of the run: ``n`` TPU chips or more, or ``NoChip``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the executables JAX builds or loads from the cache while it is
    open."""

    def __init__(self):
        self.count = 0
        self._callback = self._listen

    def _listen(self, event, *args, **kwargs):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._callback)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._callback)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_START, control_mode=None) -> dict:
    """Set-up, window, check.  Returns the result object (without printing);
    ``devices`` are the chips the run may use."""
    import jax

    from bench.harness import check as checklib
    from bench.harness import layers
    from bench.harness import peaks as peakslib
    from bench.harness import spec as speclib
    from bench.harness import trace as tracelib

    runner = speclib.driver(cell.traffic["path"])(cell.cfg, cell.traffic,
                                                  seed)
    runner.setup()
    setup_s = time.perf_counter() - t_start
    summary, logdir = None, None
    with CompileCounter() as counter:
        if trace:
            seconds = min(seconds, cell.traffic["trace_seconds"])
            logdir = tempfile.mkdtemp(prefix="bench-trace-")
            with jax.profiler.trace(logdir):
                with jax.profiler.TraceAnnotation("bench.window"):
                    stats = runner.window(seconds)
        else:
            with jax.profiler.TraceAnnotation("bench.window"):
                stats = runner.window(seconds)
    in_window = counter.count
    peak_bytes = memory_peak(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    ctx = {"cfg": cell.cfg, "traffic": cell.traffic, "stats": stats,
           "setup_s": setup_s, "program": runner.program}
    breakdown = None
    if trace:
        try:
            xplane = tracelib.find_xplane(logdir)
            summary = tracelib.load(xplane)
            reduce_s = layers.attach(summary, xplane)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        ctx.update(summary=summary, peak=peakslib.peaks(dev.device_kind),
                   notes=[f"scope and span reduction: {reduce_s:.3f} s"])
        layers.note_idle_by_span(ctx)
        device["busy_s"] = summary.busy_ns * 1e-9
        device["window_s"] = summary.window_ns * 1e-9
        breakdown = tracelib.breakdown(summary)
    print(f"window: {stats['completed']} {runner.unit}s completed in "
          f"{stats['window_s']:.3f} s; {stats['emulated_steps']} emulated "
          f"steps; compilations in the window: {in_window}", flush=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = speclib.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for note in stats.get("notes", []) + ctx.get("notes", []):
        print(note, flush=True)

    runner.release()
    readings = runner.check(control_mode=control_mode)
    checks, held = checklib.verdict(
        readings, checklib.limits(cell.cfg, cell.traffic))
    correct = held and stats["completed"] > 0 and in_window == 0
    result = {"correct": correct, "attempted": stats["completed"],
              "failed": stats["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["readings"] = readings
    return result


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import spec as speclib

    cell = speclib.resolve(speclib.load_benchmark(ROOT), args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing run", file=sys.stderr)
        return 2
    enable_cache()
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace),
                  devices[:cell.chips]))
    return 0


def emit(result: dict) -> None:
    """Print the readings, then each number compared beside its limit as
    the last lines of standard error, then the result as the last line of
    standard output (the comparisons under ``checks``, its last key)."""
    readings = result.pop("readings")
    print("readings: " + json.dumps(readings), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
