"""Readings of the program and of its control, seed by seed, at a cell's own
size, in one process: the two readings every limit in the configuration
files is set from.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

Run on the chip from the root of a checkout.  For each seed it builds the
cell, drives a short window of its traffic at the cell's own load, and runs
the comparison twice over what the window produced: the reference at the
configuration's stated precision against the program (the lower reading of
each number), and the control -- the reference one precision below, in the
program's place -- against the reference (the upper reading).  The last
line of standard output is a JSON object with both readings per seed and,
per number, the largest program reading and the smallest control reading.
The benchmark's own runs never run the control.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", nargs="*", default=None,
                   help="precisions of the control (default: the one below "
                        "the stated one)")
    args = p.parse_args(argv)

    from bench import run as runmod
    from bench.harness import check, reference, spec

    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload)
    try:
        runmod.require_chips(cell.chips)
    except runmod.NoChip as e:
        print(f"bench/control.py: {e}; nothing run", file=sys.stderr)
        return 2
    runmod.enable_cache()
    stated = reference.contraction_mode(cell.cfg,
                                        per_slot=cell.traffic["plastic"])
    controls = args.controls or [reference.CONTROL_OF[stated]]
    limits = check.limits(cell.cfg, cell.traffic)
    per_seed = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        runner = spec.driver(cell.traffic["path"])(cell.cfg, cell.traffic,
                                                   seed)
        runner.setup()
        stats = runner.window(args.seconds)
        runner.release()
        readings = {}
        for mode in controls:
            r = runner.check(control_mode=mode)
            readings.update({k: v for k, v in r.items()
                             if not k.startswith("control_")})
            readings.update({k.replace("control_", f"control[{mode}]_", 1): v
                             for k, v in r.items() if k.startswith("control_")})
        per_seed[seed] = readings
        print(json.dumps({"seed": seed, "completed": stats["completed"],
                          "seconds": time.perf_counter() - t0,
                          "readings": readings}), flush=True)
        del runner
    summary = {}
    for name in limits:
        prog = [r[name] for r in per_seed.values() if name in r]
        row = {"program_max": max(prog) if prog else None}
        for mode in controls:
            key = f"control[{mode}]_{name}"
            ctl = [r[key] for r in per_seed.values() if key in r]
            row[f"control_min[{mode}]"] = min(ctl) if ctl else None
        summary[name] = row
    print(json.dumps({"workload": args.workload, "stated": stated,
                      "controls": controls, "per_seed": per_seed,
                      "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
