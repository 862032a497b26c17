#!/usr/bin/env python3
"""Benchmark harness: run one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration, a traffic mix and a chip count; the
configuration names the system kind, whose general code
(``bench/systems/<system>.py``) builds the system under test from the
repository's program, warms up the shapes the traffic uses (set-up), drives
the traffic for ``--seconds`` (the window), and checks what the window
produced against the configuration's plain reference. Each metric is read
from the window's observations by its own reader (``bench/metrics``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, from a run whose window is traced by the profiler. The
last line of standard output is one JSON object; the numbers compared for
``correct`` come last there and as the last lines of standard error. A
run with no TPU, or with fewer chips than the cell asks for, prints no
result and exits non-zero.
"""
import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# one fixed cache directory inside the checkout, whatever the environment
# says: the program's executable cache and JAX's persistent cache both
# live there, so only the first run of a cell in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".aot_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    from harness import manifest, device, runner
    try:
        cell = manifest.resolve(args.workload, manifest.load_manifest(ROOT),
                                ROOT)
    except manifest.ManifestError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devs = device.require_chips(cell.chips)
        peaks = device.peaks(devs[0].device_kind)
    except (device.NoChip, device.UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = runner.run_cell(cell, devs, peaks, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
