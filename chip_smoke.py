#!/usr/bin/env python3
"""Smoke run of both main paths on a TPU, through the entry points a user
calls, with every result checked.

    python3 chip_smoke.py [--seed N]      # one chip: launch + serve
    python3 chip_smoke.py --chips 4       # four chips: the launch path only

* launch — the paper's path: ``LLMapReduce(wave_size="auto")`` over a
  ``PipelinedBackend`` (input buffers donated) launches 16,000 instances
  of one jitted program (a (64, 512) bf16 input through 8 layers of
  ``tanh(x @ W)``, W shared), about 1 GB of inputs. Every result is
  checked against a float32 NumPy reference. Prints the time to the first
  and to the last result of a warm launch; compile time is set-up.
* serve — ``PagedServeEngine`` (default ``kernel="auto"``: the Pallas
  paged-attention kernel on a TPU) with ``AdmissionScheduler``,
  ``ArrayBackend`` and ``CompileCache``, serving qwen3-14b at its
  published widths cut to 10 of 40 layers (one stage of a 4-stage
  pipeline over a v5e 2x2 host), random weights from ``--seed``. The same
  prompts then go through ``kernel="gather"``, and the prefill and first
  decode logits of both paths must agree.
* ``--chips 4`` — the launch path on four chips, once as one
  ``PipelinedBackend`` whose waves are sharded over a 4-chip mesh and once
  as a ``DistributedBackend`` of 4 thread nodes owning one chip each, both
  checked against the same launch on one chip.

The last line of standard output is ``{"ok": true, "device": {...}}``;
it is printed only when every phase passed. Without a TPU, or without the
repository next to this file, the script exits non-zero and prints no
result. One process holds the chip: nothing here starts another.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

# launch phase: the paper's instance count and a real device program
N_INSTANCES = 16_000
ITEM = (64, 512)
LAYERS = 8
# each layer rounds its output to bf16 (half an ulp: 2^-9 for |h| < 1) and
# a random W with unit-variance columns has a gain below 2, so 8 layers
# stay within 8 x 2 x 2^-9 = 2^-5 of the float32 reference
LAUNCH_ATOL = 2.0 ** -5

# serve phase: qwen3-14b widths, one pipeline stage's depth
SERVE_LAYERS = 10
SLOTS, PAGE, VCAP = 8, 16, 2048
PROMPT_MIN, PROMPT_MAX, GEN = 128, 1024, 32
# Pallas vs gather logits: both paths attend in bf16 with float32
# statistics but sum the softmax in different orders, so each layer's
# attention output may land one bf16 ulp apart; over 10 layers of the
# residual stream that moves a logit by a few ulps of the largest logit.
# Allowed: 2^-4 of the largest |logit| (8 ulps at the top of the range).
LOGIT_RTOL = 2.0 ** -4


def log(msg: str) -> None:
    print(msg, flush=True)


def _repo():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, src)


def _tpu_or_exit():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); this smoke runs only on a chip",
              file=sys.stderr)
        sys.exit(3)
    return devs


# ----------------------------------------------------------------------
# launch
# ----------------------------------------------------------------------

def _launch_data(seed: int):
    import ml_dtypes
    import numpy as np
    rng = np.random.default_rng(seed)
    bf16 = ml_dtypes.bfloat16
    x = rng.standard_normal((N_INSTANCES,) + ITEM, np.float32).astype(bf16)
    w = (rng.standard_normal((ITEM[1], ITEM[1]), np.float32)
         / np.sqrt(ITEM[1])).astype(bf16)
    return x, w


def _make_app(w):
    import jax.numpy as jnp
    wj = jnp.asarray(w)

    def app(x):
        for _ in range(LAYERS):
            x = jnp.tanh(x @ wj)
        return x

    return app


def _launch_reference(x, w):
    """float32 NumPy forward of every instance (threads over row blocks:
    BLAS and the tanh ufunc release the interpreter lock)."""
    import numpy as np
    wf = w.astype(np.float32)
    rows = x.reshape(-1, ITEM[1])
    out = np.empty(rows.shape, np.float32)

    def block(lo, hi):
        h = rows[lo:hi].astype(np.float32)
        for _ in range(LAYERS):
            h = np.tanh(h @ wf)
        out[lo:hi] = h

    step = 1 << 15
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as ex:
        list(ex.map(lambda lo: block(lo, min(lo + step, len(rows))),
                    range(0, len(rows), step)))
    return out.reshape(x.shape)


def _max_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out)
    if out.shape != ref.shape:
        raise AssertionError(f"output shape {out.shape} != {ref.shape}")
    err = 0.0
    for lo in range(0, len(ref), 1000):
        d = np.abs(out[lo:lo + 1000].astype(np.float32) - ref[lo:lo + 1000])
        if not np.all(np.isfinite(d)):
            raise AssertionError("non-finite launch output")
        err = max(err, float(d.max()))
    return err


def _launch(backend, app, x, tag: str, kind: str) -> tuple:
    """Cold launch (compiles every wave shape: set-up) then a warm launch
    (timed); returns the warm outputs."""
    from repro.core.llmr import LLMapReduce
    llmr = LLMapReduce(wave_size="auto", backend=backend)
    cache = backend.cache
    c0 = cache.stats["compile_s"]
    t0 = time.perf_counter()
    _, cold = llmr.map_reduce(app, x)
    log(f"[launch {tag}] cold run {time.perf_counter() - t0:.3f} s, "
        f"compile {cache.stats['compile_s'] - c0:.3f} s (set-up), "
        f"{cold.waves} waves")
    c1 = cache.stats["compile_s"]
    out, rep = llmr.map_reduce(app, x)
    # the wave controller sizes waves from measured timings, so a second
    # launch may pick wave shapes the first did not compile: that compile
    # time is reported apart from the run's own (summed over the threads
    # that compiled; nodes compile in parallel)
    compile_s = cache.stats["compile_s"] - c1
    sources = sorted({r.extra.get("compile_source") for r in rep.records})
    log(f"[launch {tag}] {kind}: {rep.n_instances} instances, "
        f"{rep.waves} waves, first result {rep.t_first_result:.4f} s, "
        f"last result {rep.t_total:.4f} s, "
        f"{rep.n_instances / rep.t_total:.1f} instances/s; compiling wave "
        f"shapes new to this run took {compile_s:.3f} s (summed over "
        f"compiling threads; executables from {sources})")
    return out, rep


def phase_launch(seed: int, kind: str):
    from repro.core.backend import PipelinedBackend
    x, w = _launch_data(seed)
    log(f"[launch] {N_INSTANCES} instances x {ITEM} bf16 = "
        f"{x.nbytes / 1e9:.3f} GB of inputs, {LAYERS} layers of "
        f"tanh(x @ W), W {w.shape}")
    app = _make_app(w)
    backend = PipelinedBackend()
    if not backend.donate:
        raise AssertionError("PipelinedBackend did not enable donation")
    out, _ = _launch(backend, app, x, "1 chip", kind)
    t0 = time.perf_counter()
    ref = _launch_reference(x, w)
    err = _max_err(out, ref)
    log(f"[launch] all {N_INSTANCES} results vs float32 reference: "
        f"max |err| {err:.6f} (limit {LAUNCH_ATOL}; reference took "
        f"{time.perf_counter() - t0:.1f} s on the host)")
    if err > LAUNCH_ATOL:
        raise AssertionError(f"launch max |err| {err} > {LAUNCH_ATOL}")
    log(f"[launch] compile cache {backend.cache.stats} "
        f"last_error={backend.cache.last_error}")
    return x, w, app, out


# ----------------------------------------------------------------------
# four chips (launch only)
# ----------------------------------------------------------------------

def _vs_one_chip(out, one, tag: str) -> str:
    """Same program, same inputs, other wave shapes: the results may differ
    only by the bf16 rounding a different batching of the matmuls can
    bring, bounded like the reference check."""
    import numpy as np
    out, one = np.asarray(out), np.asarray(one)
    same = int(np.sum(np.all(out == one, axis=(1, 2))))
    err = _max_err(out, one.astype(np.float32))
    if err > LAUNCH_ATOL:
        raise AssertionError(f"{tag}: max |out - 1 chip| {err} > "
                             f"{LAUNCH_ATOL}")
    return f"{same} of {len(one)} bit-identical, max |diff| {err:.6f}"


def phase_launch_4(seed: int, kind: str) -> None:
    import jax
    import numpy as np
    from repro.core.backend import PipelinedBackend
    from repro.core.compile_cache import CompileCache
    from repro.dist.backend import DistributedBackend
    from repro.launch.mesh import make_host_mesh
    x, _, app, one = phase_launch(seed, kind)
    want = {d.id for d in jax.devices()}

    # (a) one backend, every wave sharded over the 4-chip mesh
    mesh = make_host_mesh()
    be = PipelinedBackend(mesh=mesh)
    out, _ = _launch(be, app, x, "mesh 4 chips", kind)
    diff = _vs_one_chip(out, one, "mesh 4 chips")
    wave, _ = be.dispatch(app, x[:1000], 1000).result()
    got = {d.id for d in wave.sharding.device_set}
    log(f"[launch mesh 4 chips] outputs vs 1 chip: {diff}; a "
        f"1000-instance wave's output lives on devices {sorted(got)}")
    if got != want:
        raise AssertionError(f"mesh wave on {got}, wanted {want}")

    # (b) four thread nodes, each owning one chip
    dist = DistributedBackend(n_nodes=4, cache=CompileCache(),
                              heartbeat_timeout_s=60.0)
    try:
        out, _ = _launch(dist, app, x, "4 nodes x 1 chip", kind)
        diff = _vs_one_chip(out, one, "4 nodes x 1 chip")
        placed = {}
        for nid, agent in sorted(dist.agents.items()):
            wave, _ = agent.backend.dispatch(app, x[:8], 8).result()
            placed[nid] = sorted(d.id for d in wave.sharding.device_set)
        reported = {nid: (info.get("device") or {}).get("devices")
                    for nid, info in sorted(dist.registry.rollup().items())}
        log(f"[launch 4 nodes x 1 chip] outputs vs 1 chip: {diff}; "
            f"wave outputs per node on devices {placed}; nodes reported "
            f"{reported}")
        chips = [ids[0] for ids in placed.values()]
        if (any(len(ids) != 1 for ids in placed.values())
                or set(chips) != want or placed != reported):
            raise AssertionError(f"nodes did not own one chip each: "
                                 f"{placed} / {reported}")
    finally:
        dist.close()


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------

def serve_config():
    """qwen3-14b at published widths, cut to one 4-stage pipeline stage."""
    from repro.configs.qwen3_14b import CONFIG
    return CONFIG.replace(groups=tuple(
        dataclasses.replace(g, repeats=SERVE_LAYERS) for g in CONFIG.groups))


def _requests(seed: int, vocab: int):
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, SLOTS)
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(n)),
                    max_new=GEN) for i, n in enumerate(lens)]


def _serve(cfg, params, seed: int, kernel: str, kind: str, cache):
    import numpy as np
    from repro.core.backend import ArrayBackend
    from repro.serve.engine import PagedServeEngine
    from repro.serve.scheduler import AdmissionScheduler
    c0, h0 = cache.stats["compile_s"], cache.stats["disk_hits"]
    eng = PagedServeEngine(cfg, params, slots=SLOTS, page_size=PAGE,
                           pages_per_slot=VCAP // PAGE,
                           backend=ArrayBackend(cache=cache),
                           scheduler=AdmissionScheduler(), kernel=kernel)
    first = {}

    def sink(req, row):
        got = first.setdefault(req.rid, [])
        if len(got) < 2:                  # prefill + first decode step
            got.append(np.asarray(row, np.float32))

    eng.logit_sink = sink
    eng.run(_requests(seed, cfg.vocab))           # cold: compiles, set-up
    compile_s = cache.stats["compile_s"] - c0
    eng.logit_sink = None
    reqs = _requests(seed, cfg.vocab)
    stats = eng.run(reqs)                         # warm: timed
    if not all(r.done and len(r.out) == GEN for r in reqs):
        raise AssertionError(f"{kernel}: not every request finished")
    ttft = sorted(r.t_first - r.t_enqueue for r in reqs)
    log(f"[serve {kernel}] {kind}: engine kernel={eng.kernel}, "
        f"{len(reqs)} requests, prompts "
        f"{sorted(len(r.prompt) for r in reqs)}, {GEN} new tokens each")
    log(f"[serve {kernel}] {kind}: TTFT median {ttft[len(ttft) // 2]:.4f} s "
        f"max {ttft[-1]:.4f} s; {stats['decoded']} tokens in "
        f"{stats['wall_s']:.4f} s = {stats['decoded'] / stats['wall_s']:.1f} "
        f"tokens/s; {stats['steps']} decode steps, "
        f"{stats['prefill_dispatches']} prefill dispatches (warm)")
    log(f"[serve {kernel}] compile {compile_s:.3f} s (set-up); disk hits "
        f"{cache.stats['disk_hits'] - h0}; executables "
        f"{stats['compile_sources']}")
    kernel_used = eng.kernel
    del eng
    gc.collect()
    return first, kernel_used


def phase_serve(seed: int, kind: str) -> None:
    import jax
    import numpy as np
    from repro.core.compile_cache import CompileCache
    from repro.models import attention
    from repro.models.lm import count_params, lm_init
    cfg = serve_config()
    log("[serve] config qwen3-14b: d_model 5120, 40 heads, 8 kv heads, "
        "head_dim 128, d_ff 17408, vocab 151936, bf16, untied embeddings")
    log(f"[serve] cut: layers 40 -> {SERVE_LAYERS} (one stage of a 4-stage "
        f"pipeline over a v5e 2x2 host)")
    log("[serve] cut: none to widths; both vocab tables (embed, lm_head) "
        "kept whole")
    log(f"[serve] cut: weights random from seed {seed}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: lm_init(k, cfg))(jax.random.PRNGKey(seed)))
    nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    log(f"[serve] {count_params(cfg) / 1e9:.3f} B parameters, "
        f"{nbytes / 1e9:.3f} GB, made on the chip in "
        f"{time.perf_counter() - t0:.1f} s (set-up); KV pool {SLOTS} slots x "
        f"{VCAP} tokens, page size {PAGE}")
    if attention._paged_interpret():
        raise AssertionError("paged kernel would run in interpret mode")
    cache = CompileCache()
    log(f"[serve] compile cache dir {cache.cache_dir}")
    pallas, used = _serve(cfg, params, seed, "auto", kind, cache)
    if used != "pallas":
        raise AssertionError(f"kernel='auto' chose {used!r} on a TPU")
    gather, _ = _serve(cfg, params, seed, "gather", kind, cache)
    worst, compared, ties = 0.0, 0, 0
    for rid in sorted(gather):
        for step, (a, b) in enumerate(zip(pallas[rid], gather[rid])):
            if not (np.all(np.isfinite(a)) and a.shape == (cfg.vocab,)):
                raise AssertionError(f"rid {rid} step {step}: bad logits")
            tol = LOGIT_RTOL * float(np.abs(b).max())
            err = float(np.abs(a - b).max())
            worst = max(worst, err / tol * LOGIT_RTOL)
            compared += 1
            if err > tol:
                raise AssertionError(
                    f"rid {rid} {('prefill', 'decode 1')[step]}: pallas vs "
                    f"gather max |dlogit| {err:.5f} > {tol:.5f}")
            if int(a.argmax()) != int(b.argmax()):
                # the two paths fed different tokens to decode: allowed
                # only on a top-2 tie within the tolerance, and the next
                # step's logits are then not comparable
                top2 = np.sort(b)[-2:]
                if top2[1] - top2[0] > tol:
                    raise AssertionError(f"rid {rid}: greedy tokens differ "
                                         f"without a tie")
                ties += 1
                break
    log(f"[serve] pallas vs gather, prefill + first decode logits: "
        f"{compared} rows of {len(gather)} requests, max |dlogit| / max "
        f"|logit| = {worst:.6f} (limit {LOGIT_RTOL}); {ties} greedy ties")
    log(f"[serve] compile cache {cache.stats} last_error={cache.last_error}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the launch path across four chips, "
                         "against the same launch on one")
    args = ap.parse_args()
    _repo()
    devs = _tpu_or_exit()
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 3
    kind = f"{devs[0].device_kind} x{len(devs)}"
    log(f"[smoke] devices: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}); JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_launch_4(args.seed, kind)
    else:
        phase_launch(args.seed, kind)
        gc.collect()
        phase_serve(args.seed, kind)
    log(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
