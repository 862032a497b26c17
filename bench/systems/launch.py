"""General code for launch cells: a closed loop of whole launches through
``LLMapReduce.map_reduce``, the paper's entry point.

Configuration (``system: launch``): the instance count, the application
(``app``: a device program every instance runs on its own small argument
vector) and the launcher's wave sizing; launches go through the pipelined
backend. Traffic
(``loop: closed``): one user relaunches as soon as the launch returns,
and each launch's arguments shift along one seeded base set, so no wave
ever repeats an input that an earlier launch staged.

Set-up makes the inputs on the device from the seed, builds the backend
and launches until a launch compiles and loads nothing new (up to the
traffic's cap). The window then launches back to back for the run's
seconds; every launch started in the window completes and counts. After
the window a seeded sample of every launch's outputs is checked against
the configuration's plain float32 reference. In a control run the
reference, one precision step below the configuration's, takes the
application's place in every launch.
"""
from __future__ import annotations

import time
from typing import Callable, List

import numpy as np


def jax_key(seed: int, salt: int = 0):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    import jax
    word = np.random.SeedSequence([seed, salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make_app(w: np.ndarray, layers: int) -> Callable:
    """The instance program: ``layers`` of ``tanh(x @ W)``, W shared."""
    import jax.numpy as jnp
    wj = jnp.asarray(w)

    def app(x):
        for _ in range(layers):
            x = jnp.tanh(x @ wj)
        return x

    return app


def make_inputs(seed: int, n: int, item_shape, dtype: str) -> np.ndarray:
    """The base input set, made on the device in one call and brought to
    the host, where a launch's wave loader reads it."""
    import jax
    import jax.numpy as jnp
    shape = (n,) + tuple(item_shape)
    make = jax.jit(lambda k: jax.random.normal(k, shape, jnp.dtype(dtype)))
    x = make(jax_key(seed, 1))
    out = np.asarray(jax.device_get(x))
    del x
    return out


def run(cell, ctx) -> dict:
    from repro.core.backend import PipelinedBackend
    from repro.core.compile_cache import CompileCache
    from repro.core.llmr import LLMapReduce
    cfg, traffic = cell.config, cell.traffic
    app_cfg, launcher = cfg["app"], cfg["launcher"]
    n = int(cfg["instances"])
    shift = int(traffic["shift_per_launch"])
    max_launches = int(traffic["max_launches"])
    warm_cap = int(traffic["warmup_launches_max"])
    ref = cell.reference()

    # -- set-up ---------------------------------------------------------
    w = ref.app_weight(app_cfg)
    layers = int(app_cfg["layers"])
    app = ref.control_app(w, layers) if ctx.control else make_app(w, layers)
    base = make_inputs(ctx.seed, n + shift * (warm_cap + max_launches),
                       app_cfg["item_shape"], app_cfg["dtype"])
    backend = PipelinedBackend(cache=CompileCache())
    cache = backend.cache
    llmr = LLMapReduce(wave_size=launcher["wave_size"], backend=backend)
    k_next = 0

    def loader(k: int):
        off = k * shift

        def load(lo, hi):
            with ctx.annotate("load_inputs"):
                return base[off + lo: off + hi]
        return load

    def launch(k: int) -> dict:
        c0 = cache.stats["compile_s"]
        m0, d0 = cache.stats["misses"], cache.stats["disk_hits"]
        t0 = time.perf_counter()
        with ctx.annotate("map_reduce"):
            out, rep = llmr.map_reduce(app, loader(k), n_tasks=n)
        t1 = time.perf_counter()
        return {"k": k, "out": out, "t_s": t1 - t0,
                "first_s": rep.t_first_result, "waves": rep.waves,
                "instances": rep.n_instances,
                "compile_s": cache.stats["compile_s"] - c0,
                "new_programs": (cache.stats["misses"] - m0
                                 + cache.stats["disk_hits"] - d0),
                "host_submit_s": sum(r.t_schedule + r.t_stage + r.t_dispatch
                                     for r in rep.records)}

    warm = 0
    while warm < warm_cap:
        rec = launch(k_next)
        k_next += 1
        warm += 1
        if rec["new_programs"] == 0:
            break
    del rec

    # -- window -----------------------------------------------------
    launches: List[dict] = []
    samples: List[tuple] = []
    rng = np.random.default_rng([ctx.seed, 7])
    n_check = int(cfg["check_sample"])
    t_end = ctx.begin_window() + ctx.seconds
    while time.perf_counter() < t_end and len(launches) < max_launches:
        rec = launch(k_next)
        out = rec.pop("out")
        idx = np.sort(rng.choice(n, size=min(n_check, n), replace=False))
        ok_shape = tuple(out.shape) == (n,) + tuple(app_cfg["item_shape"])
        samples.append((k_next, idx, out[idx].copy() if ok_shape
                        else None))
        del out
        launches.append(rec)
        k_next += 1
    ctx.end_window()
    ctx.read_memory_peak()

    # -- check ----------------------------------------------------------
    worst, missing = 0.0, 0
    for k, idx, got in samples:
        if got is None:
            missing += 1
            continue
        x = base[k * shift + idx]
        want = ref.reference(x, w, layers)
        worst = max(worst, ref.max_abs_err(got, want))
    limits = cfg["limits"]
    return {
        "obs": {"launches": launches, "instances": n},
        "attempted": len(launches), "failed": missing,
        "checks": {"max_abs_err": (worst, limits["max_abs_err"]),
                   "launches_short": (missing, 0)},
    }
