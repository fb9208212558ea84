"""Benchmark orchestrator — one module per paper table/figure/claim.

Prints ``name,us_per_call,derived`` CSV lines per benchmark, writes
``BENCH_interconnect.json`` (name → us_per_call) for the routing datapath,
stamps the recording environment next to the numbers (``_environment`` key:
python/jax versions, cpu count, platform, and a fixed calibration
microbenchmark), and appends every run to ``BENCH_history.jsonl`` — so
cross-container drift (PR 4's 938→3750 µs re-record) is machine-diagnosable
from the calibration ratio instead of a prose footnote.

  fig5_latency            Fig 5A  latency distributions vs rate (3:1 fan-in)
  fig5_speedup            Fig 5B  speed-up factor vs routing latency
  encoding_tradeoff       §III    8b10b@5G vs 64b66b@8G
  scaling_projection      §V      120-chip second-layer projection
  interconnect_throughput §III    routing datapath throughput
  stream                  §III/§V streaming engine vs per-step dispatch
                                  (star, two-layer, 3-level EXT_4CASE fabric)
  stream_timed            §IV     timed streaming datapath (timestamp lane)
  stream_degraded         §III    degraded-mode fabric: dead uplinks,
                                  extension-lane detours, reroute exhaustion
  stream_ckpt             §III    durable long-run streams: crash-consistent
                                  checkpoint cost + windowed-supervision
                                  overhead (full plastic stream state)
  stream_routed           §III/§V routed exchange mode (ppermute edge
                                  schedule) vs broadcast gather: parity
                                  gate + interleaved same-run timing
  stream_engine           §IV     emulation-as-a-service: S tenant sessions
                                  batched through one compiled window
                                  program (parity gate + experiments/s vs
                                  the sequential one-at-a-time baseline)
  moe_dispatch            DESIGN §4  event-frame dispatch at LM scale
  roofline_table          §Roofline  all dry-run cells (needs results/)
"""

import argparse
import datetime
import json
import os
import sys
import time
import traceback

from benchmarks import (encoding_tradeoff, engine_throughput, exchange_stream,
                        fig5_latency, fig5_speedup, grad_compression,
                        interconnect_throughput, moe_dispatch, roofline_table,
                        scaling_projection)
from repro.launch.compile_cache import enable_compile_cache

ALL = [
    ("fig5_latency", fig5_latency.run),
    ("fig5_speedup", fig5_speedup.run),
    ("encoding_tradeoff", encoding_tradeoff.run),
    ("scaling_projection", scaling_projection.run),
    ("interconnect_throughput", interconnect_throughput.run),
    ("stream", exchange_stream.run),
    ("stream_timed", exchange_stream.run_timed),
    ("stream_degraded", exchange_stream.run_degraded),
    ("stream_ckpt", exchange_stream.run_ckpt),
    ("stream_routed", exchange_stream.run_routed),
    ("stream_engine", engine_throughput.run),
    ("moe_dispatch", moe_dispatch.run),
    ("grad_compression", grad_compression.run),
    ("roofline_table", roofline_table.run),
]
# Pre-fabric spelling of the streaming benchmark, kept for CI/scripts.
ALIASES = {"exchange_stream": "stream"}

HISTORY_JSONL = os.environ.get("BENCH_HISTORY_JSONL", "BENCH_history.jsonl")


# ---------------------------------------------------------------------------
# Environment stamping: make cross-container drift diagnosable
# ---------------------------------------------------------------------------


def _calibration_us(trials: int = 5) -> float:
    """Fixed microbenchmark (jit'd 512x512 f32 matmul + reduction), min over
    ``trials``: a machine-speed scalar recorded next to every timing, so a
    re-record on a slower/noisier container shows up as a calibration shift
    rather than a mystery regression in the datapath numbers."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(512 * 512, dtype=jnp.float32).reshape(512, 512) / 1e6
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def environment_metadata() -> dict:
    """The recording environment of a benchmark run."""
    import platform

    import jax

    return {
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count() or 0,
        "platform": platform.platform(),
        "calibration_matmul_us": round(_calibration_us(), 3),
    }


def stamp_environment(bench_json: str | None = None,
                      history_jsonl: str | None = None, *,
                      ran: list[str] | None = None,
                      failures: list[str] | None = None,
                      errors: dict[str, str] | None = None) -> dict:
    """Write ``_environment`` into the benchmark JSON and append the full
    run record (environment + results + what ran) to the history log.

    ``errors`` maps a failed benchmark name to the tail of its traceback;
    it is stamped as an ``_errors`` block next to the numbers (and cleared
    again by the next clean run), so a red CI artifact carries its own
    diagnosis instead of requiring the job log.
    """
    bench_json = bench_json or interconnect_throughput.BENCH_JSON
    history_jsonl = history_jsonl or HISTORY_JSONL
    payload = {}
    if os.path.exists(bench_json):
        with open(bench_json) as f:
            payload = json.load(f)
    env = environment_metadata()
    payload["_environment"] = env
    payload.pop("_errors", None)
    if errors:
        payload["_errors"] = errors
    with open(bench_json, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    record = {
        "utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "benchmarks": ran or [],
        "failures": failures or [],
        "errors": errors or {},
        "environment": env,
        "results": {k: v for k, v in payload.items()
                    if k not in ("_environment", "_errors")},
    }
    with open(history_jsonl, "a") as f:
        f.write(json.dumps(record, sort_keys=True) + "\n")
    return env


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        description="Run the paper benchmarks (all ten modules by default).")
    parser.add_argument(
        "--only", action="append", metavar="NAME",
        help="run only the named benchmark (repeatable); one of: "
             + ", ".join(name for name, _ in ALL))
    args = parser.parse_args(argv)
    enable_compile_cache()

    selected = ALL
    if args.only:
        wanted = {ALIASES.get(n, n) for n in args.only}
        known = {name for name, _ in ALL}
        unknown = sorted(wanted - known)
        if unknown:
            parser.error(f"unknown benchmark(s) {unknown}; "
                         f"choose from {sorted(known)}")
        selected = [(name, fn) for name, fn in ALL if name in wanted]

    failures = []
    errors: dict[str, str] = {}
    for name, fn in selected:
        print(f"\n=== {name} ===")
        try:
            fn(verbose=True)
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
            errors[name] = "".join(
                traceback.format_exc().splitlines(keepends=True)[-12:])

    env = stamp_environment(ran=[name for name, _ in selected],
                            failures=failures, errors=errors)
    print(f"\nenvironment: jax {env['jax']} / python {env['python']} / "
          f"{env['cpu_count']} cpus / calibration "
          f"{env['calibration_matmul_us']} us (history: {HISTORY_JSONL})")

    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print(f"\nall benchmarks passed "
          f"(routing datapath timings: {interconnect_throughput.BENCH_JSON})")


if __name__ == "__main__":
    main()
