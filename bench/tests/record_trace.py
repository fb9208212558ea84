"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py <output directory>

Run on one TPU chip from the root of a checkout.  It drives the tiny
stream configuration of ``bench/tests/data`` through two chained calls
under the profiler, with the benchmark's own ``bench.*`` spans, and writes
the profiler's output under the directory given.
"""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

DATA = pathlib.Path(__file__).with_name("data")


def main(out: str) -> int:
    import jax

    from bench.harness import spec

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: no TPU; nothing recorded", file=sys.stderr)
        return 2
    cfg = json.loads((DATA / "tiny_projected_120chip.json").read_text())
    traffic = json.loads((DATA / "tiny_stream_timed.json").read_text())
    cell = spec.driver("stream")(cfg, traffic, seed=7)
    cell.setup()
    cell.window(0.05)                      # one more warm pass, untraced
    with jax.profiler.trace(out):
        with jax.profiler.TraceAnnotation("bench.window"):
            stats = cell.window(0.0)
    print(json.dumps({k: v for k, v in stats.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
