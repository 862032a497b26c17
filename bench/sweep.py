#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest arrival rate the system
sustains. One process sets the cell up once and drives its traffic at
each rate in turn, printing one JSON line per rate.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 0.3,0.5,0.7

Run once when a cell is defined (on the chip); the rate the cell keeps is
written into its traffic file. Not part of a benchmark run.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".aot_cache")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device, manifest, runner, stats, traffic as gen
    cell = manifest.resolve(args.workload, manifest.load_manifest(ROOT), ROOT)
    device.require_chips(cell.chips)
    drv = manifest.load_module(manifest.system_path(ROOT, cell.system))
    cfg, params, engine = drv.build(cell, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(cell.traffic, rate_per_s=rate)
        arrivals = gen.schedule(tr, args.seconds, args.seed, cfg.vocab)
        reqs, due, window = drv.drive(engine, arrivals, runner.Untimed(),
                                      args.seconds, float(tr["drain_s"]))
        recs = drv.records(reqs, due)
        ok = [r for r in recs if r["ok"]]
        row = {"rate_per_s": rate, "requests": len(recs),
               "finished": len(ok), "backlog_at_close": window["backlog"],
               "drain_s": window["drain_s"],
               "decoded": window["decoded"], "steps": window["steps"]}
        if ok:
            for k in ("ttft_s", "tpot_s"):
                xs = [r[k] for r in ok]
                row[k + "_p50"] = stats.nearest_rank(xs, 0.5)
                row[k + "_p95"] = stats.nearest_rank(xs, 0.95)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
