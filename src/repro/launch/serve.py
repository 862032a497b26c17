"""Serving launcher CLI: continuous-batching decode for any architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --requests 8 --gen-len 16

Default engine is the paged one (shared KV page pool, batched multi-slot
prefill, priority classes); ``--engine fixed`` runs the statically
partitioned baseline. ``--batch-frac`` marks a fraction of the trace as
batch-class filler so the priority split shows up in the per-class
TTFT/TPOT table.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core.backend import ArrayBackend
from repro.core.compile_cache import CompileCache
from repro.core.telemetry import serve_table
from repro.models.lm import lm_init
from repro.serve.engine import PagedServeEngine, Request, ServeEngine
from repro.serve.scheduler import AdmissionScheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("paged", "fixed"), default="paged")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--capacity", type=int, default=128,
                    help="per-request KV rows (fixed: per-slot ring; paged: "
                         "pages_per_slot * page_size virtual capacity)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="shared pool size in pages (default: "
                         "slots * capacity / page_size, i.e. no "
                         "oversubscription; smaller pools admit more "
                         "requests than they can hold and preempt "
                         "batch-class work under pressure)")
    ap.add_argument("--batch-frac", type=float, default=0.0,
                    help="fraction of requests enqueued as batch-class")
    ap.add_argument("--one-slot-prefill", action="store_true",
                    help="paged engine: disable batched multi-slot prefill")
    ap.add_argument("--target-first-result-s", type=float, default=None,
                    help="interactive first-result SLO: ONE knob, wired "
                         "end-to-end — gates admission preemption of "
                         "batch-class work here AND rides the backend to "
                         "any WaveController built over it, capping "
                         "launch-side wave sizing at the same target")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent AOT compile cache dir (default: "
                         "$JAX_COMPILATION_CACHE_DIR, else .aot_cache in "
                         "the checkout); a warm dir skips trace+compile "
                         "entirely")
    ap.add_argument("--no-cache-spill", action="store_true",
                    help="keep the compile cache in memory only")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    t0 = time.perf_counter()
    params = lm_init(jax.random.PRNGKey(0), cfg)
    print(f"loaded {cfg.name} in {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=args.prompt_len),
                    max_new=args.gen_len,
                    priority=("batch" if rng.random() < args.batch_frac
                              else "interactive"))
            for i in range(args.requests)]
    cache = CompileCache(cache_dir=args.cache_dir,
                         persistent=not args.no_cache_spill)
    # the SLO knob is set ONCE, on the shared backend: the admission
    # scheduler preempts against it below, and any LLMapReduce built over
    # this backend hands it to its WaveController as the t_first ceiling
    # (serve SLO -> launch wave sizing, end-to-end)
    backend = ArrayBackend(cache=cache,
                           target_first_result_s=args.target_first_result_s)
    sched = AdmissionScheduler(
        target_first_result_s=backend.target_first_result_s)
    if args.engine == "fixed":
        eng = ServeEngine(cfg, params, slots=args.slots,
                          capacity=args.capacity, backend=backend,
                          scheduler=sched)
    else:
        pages_per_slot = max(1, -(-args.capacity // args.page_size))
        eng = PagedServeEngine(cfg, params, slots=args.slots,
                               page_size=args.page_size,
                               pages_per_slot=pages_per_slot,
                               pool_pages=args.pool_pages,
                               backend=backend, scheduler=sched,
                               batched_prefill=not args.one_slot_prefill)
    stats = eng.run(reqs)
    wall = max(stats["wall_s"], 1e-9)        # instant runs: no ZeroDivision
    print(f"served {stats['admitted']} requests, {stats['decoded']} tokens "
          f"in {stats['steps']} batched steps / "
          f"{stats['prefill_dispatches']} prefill dispatches "
          f"({stats['wall_s']:.1f}s, {stats['decoded'] / wall:.0f} tok/s)")
    for cls, agg in stats.get("classes", {}).items():
        print(f"  {cls}: n={agg['n']} p50_ttft={agg['p50_ttft_s']:.3f}s "
              f"p50_tpot={agg['p50_tpot_s'] * 1e3:.1f}ms "
              f"preemptions={agg['preemptions']}")
    if "slo_attainment" in stats:
        print(f"  slo_attainment={stats['slo_attainment']:.2f} "
              f"(target_first_result_s={args.target_first_result_s})")
    if args.engine == "paged":
        ps = eng.pool_stats()
        print(f"  pool: {eng.pool.n_pages} pages x {eng.pool.page_size} "
              f"rows, watermark={ps['watermark']} "
              f"alloc_failures={ps['alloc_failures']}")
    print(serve_table(eng.records, title=f"{cfg.name} {args.engine}"))
    src = stats["compile_sources"]
    print(f"compile cache: step={src.get('step')} "
          f"prefills={sorted(v for k, v in src.items() if k != 'step')} "
          f"stats={cache.stats}")


if __name__ == "__main__":
    main()
