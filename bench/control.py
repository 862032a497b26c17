#!/usr/bin/env python3
"""The control readings that a cell's correctness limits are set from:
whole runs of the cell, on several seeds in one process, with the
configuration's reference computed one precision step below the one the
configuration states (float8 for bfloat16) in the program's place. Each
run has to come out not correct. Prints one result line per seed.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

* launch cells: the float8 reference is the application every launch
  runs; the check compares its outputs with the float32 reference.
* serve cells: the program serves the window as in a run; the check reads,
  at each position of the sampled requests' prompts and served tokens,
  the gap of the token that the float8 reference puts first.

Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".aot_cache")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device, manifest, runner
    cell = manifest.resolve(args.workload, manifest.load_manifest(ROOT), ROOT)
    devs = device.require_chips(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        row = runner.run_cell(cell, devs, peaks, seed=seed,
                              seconds=args.seconds, trace=False,
                              t_start=t0, control=True)
        row.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
