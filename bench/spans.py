#!/usr/bin/env python3
"""Name a cell's idle device time by the program's own spans: one traced
run with the program's tracer on, printing the run's result line with
its end-to-end and per-layer metrics, the span metrics of
``harness.spans`` and the idle time per span.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \\
        [--profile 0]

``--profile 0`` keeps the profiler off: the tracer's own cost then shows
in the end-to-end metrics.

The line also gives ``disabled_span_us``: what a span costs the program
while its tracer is off, measured in this process after the run. Not
part of a benchmark run.
"""
import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def disabled_span_us(n: int = 200_000) -> dict:
    """Microseconds per span site with the tracer off, less an empty
    loop's: a ``with`` span, a ``with`` child span, a start/finish pair."""
    from repro.obs.trace import TRACER
    if TRACER.enabled:
        raise RuntimeError("the tracer is on")

    def per_call(body) -> float:
        t = time.perf_counter()
        body()
        return (time.perf_counter() - t) / n * 1e6

    def empty():
        for _ in range(n):
            pass

    def span():
        for _ in range(n):
            with TRACER.span("x"):
                pass

    def child():
        for _ in range(n):
            with TRACER.child("x"):
                pass

    def start_finish():
        for _ in range(n):
            TRACER.finish(TRACER.start("x"))

    base = per_call(empty)
    return {k: per_call(f) - base for k, f in
            (("span", span), ("child", child),
             ("start_finish", start_finish))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    cache_dir = os.path.join(ROOT, ".aot_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device, manifest, spans
    cell = manifest.resolve(args.workload, manifest.load_manifest(ROOT), ROOT)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = device.require_chips(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    result = spans.run_cell(cell, devs, peaks, seed=args.seed,
                            seconds=args.seconds, t_start=T_START,
                            profile=bool(args.profile))
    result["disabled_span_us"] = disabled_span_us()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
