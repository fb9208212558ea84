"""The per-layer split of one benchmark cell's traced window, read from the
program's own named scopes and host spans.

    python3 bench/trace_layers.py --workload <name> --seed <n> \\
        [--seconds <s>] [--keep <directory>]

Run on the cell's chips from the root of a checkout.  It sets the cell up as
``bench/run.py`` does, drives its traffic under the profiler for the mix's
``trace_seconds`` at most, and prints one JSON object as the last line of
standard output: under ``metrics`` the device ms per emulated step of each
outermost scope (``<path>.<scope>_ms_per_step``) and of each scope right
inside one (``<path>.<scope>_<part>_ms_per_step``) with the program's
``unscoped`` share, the engine's readback ms and MB, accounting ms and slot
occupancy per ``step()``, and the self ms per ``step()`` of every program
span; under ``bench`` the cell's own per-layer metrics read from the same
trace by their files in ``bench/metrics/``.  Notes before it split each
scope that holds named scopes, and the device's idle time by span.  It
runs no comparison with the reference.  ``--keep`` keeps the profiler's
output in the directory given.
"""

import argparse
import json
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def layer_readings(ctx: dict) -> dict:
    """The per-layer numbers of a context whose summary carries the scope
    times and the program's spans: every outermost scope of the program
    and every scope right inside one, the engine's host readings, and the
    self time of every program span."""
    from bench.harness import layers, readers

    path = ctx["traffic"]["path"]
    paths = layers.program_paths(ctx) or {}
    out = {}
    for scope in layers.split(paths):
        out[f"{path}.{scope}_ms_per_step"] = layers.scope_ms_per_step(
            ctx, scope)
        if scope == layers.UNSCOPED:
            continue
        for part in layers.split(paths, scope):
            if part != layers.OTHER:
                out[f"{path}.{scope}_{part}_ms_per_step"] = (
                    layers.scope_ms_per_step(ctx, f"{scope}/{part}"))
    out[f"{path}.unscoped_share"] = layers.unscoped_share(ctx)
    out[f"{path}.device_ms_per_call"] = readers.program_ms_per_call(ctx)
    out[f"{path}.device_ms_per_step"] = readers.program_ms_per_step(ctx)
    out[f"{path}.idle_share"] = readers.idle_share(ctx)
    out[f"{path}.pack_kernel_share"] = readers.kernel_share(ctx, r"")
    if path == "engine":
        out["engine.readback_ms"] = layers.span_ms_per_step(
            ctx, ("engine.readback",))
        out["engine.readback_mb"] = layers.readback_mb(ctx)
        out["engine.account_ms"] = layers.span_ms_per_step(
            ctx, ("engine.account", "engine.finalize"))
        out["engine.slot_occupancy"] = layers.slot_occupancy(ctx)
        out["engine.step_host_ms"] = readers.host_ms_per_span(
            ctx, "bench.engine.step")
    for name in sorted({sp[0] for sp in ctx["summary"].program_spans or ()}):
        out[f"self_ms_per_step.{name}"] = layers.span_ms_per_step(
            ctx, (name,))
    layers.note_idle_by_span(ctx)
    return {k: v for k, v in out.items() if v is not None}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--keep", default=None)
    args = p.parse_args(argv)

    import jax

    from bench import run as runmod
    from bench.harness import layers, peaks, spec
    from bench.harness import trace as tracelib

    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload)
    try:
        devices = runmod.require_chips(cell.chips)
    except runmod.NoChip as e:
        print(f"bench/trace_layers.py: {e}; nothing run", file=sys.stderr)
        return 2
    runmod.enable_cache()
    runner = spec.driver(cell.traffic["path"])(cell.cfg, cell.traffic,
                                               args.seed)
    runner.setup()
    seconds = cell.traffic["trace_seconds"]
    if args.seconds is not None:
        seconds = min(seconds, args.seconds)
    logdir = args.keep or tempfile.mkdtemp(prefix="bench-trace-")
    with jax.profiler.trace(logdir):
        with jax.profiler.TraceAnnotation("bench.window"):
            stats = runner.window(seconds)
    xplane = tracelib.find_xplane(logdir)
    summary = tracelib.load(xplane)
    reduce_s = layers.attach(summary, xplane)
    ctx = {"cfg": cell.cfg, "traffic": cell.traffic, "stats": stats,
           "program": runner.program, "summary": summary,
           "peak": peaks.peaks(devices[0].device_kind),
           "notes": [f"scope and span reduction: {reduce_s:.3f} s"]}
    result = layer_readings(ctx)
    bench = {m["name"]: spec.reader(m["name"])(ctx) for m in cell.per_layer}
    for note in dict.fromkeys(stats.get("notes", []) + ctx.get("notes", [])):
        print(note, flush=True)
    runner.release()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "window_s": stats["window_s"],
                      "emulated_steps": stats["emulated_steps"],
                      "program_calls": stats["program_calls"],
                      "metrics": result, "bench": bench}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
