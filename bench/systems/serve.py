"""General code for serving cells: open-loop requests into the paged
server (``PagedServeEngine`` with ``AdmissionScheduler``, ``ArrayBackend``
and ``CompileCache``), driven through the public API.

Configuration (``system: serve``): the model's published keys (as in its
``config.json``, depth cut to the chip's share), ``program.arch`` naming
the program's model of that family, and the engine's slots and pages.
Traffic (``loop: open``): see ``harness.traffic``.

Set-up makes the weights on the device from the seed (the reference's
``make_weights``, one jitted call, in the program's parameter layout),
builds the engine (its decode step comes from the compile cache), and
runs one short request per prompt bucket the traffic can reach, so every
prefill shape is compiled or loaded and executed before the window.

Window: each request is enqueued at its due time
(``scheduler.enqueue(req, now=due)``) and the engine advances one
admission and one decode step per ``engine.run([], max_steps=steps+1)``.
After the window closes, requests already due are served to the end (at
most ``drain_s`` more); one that never finishes is a failure. Then a
seeded sample of finished requests, the longest among them, goes through
the configuration's float32 reference, which reads every served token's
logit against the reference's best. In a control run the check reads,
at the same positions, the gap of the token that the reference one
precision step below the configuration's puts first.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from harness import traffic as gen
from harness.work import DenseLM


def model_config(hf: dict, program: dict):
    """The program's model for the configuration file, checked against
    the file's published widths."""
    from repro.configs import get_config
    cfg = get_config(program["arch"], smoke=bool(program.get("smoke")))
    cfg = cfg.replace(groups=tuple(
        dataclasses.replace(g, repeats=int(hf["num_hidden_layers"]))
        for g in cfg.groups))
    blk = cfg.groups[0].pattern[0]
    have = {"hidden_size": cfg.d_model, "vocab_size": cfg.vocab,
            "num_attention_heads": blk.attn.n_heads,
            "num_key_value_heads": blk.attn.n_kv_heads,
            "head_dim": blk.attn.head_dim,
            "intermediate_size": blk.mlp.d_ff,
            "rope_theta": blk.attn.rope_theta,
            "rms_norm_eps": cfg.norm_eps,
            "tie_word_embeddings": cfg.tie_embeddings,
            "num_hidden_layers": sum(g.repeats for g in cfg.groups)}
    bad = {k: (v, hf[k]) for k, v in have.items() if v != hf[k]}
    if bad or len(cfg.groups) != 1 or len(cfg.groups[0].pattern) != 1:
        raise ValueError(f"program model {program['arch']} differs from "
                         f"the configuration: {bad}")
    return cfg


def dense_lm(hf: dict) -> DenseLM:
    return DenseLM(d_model=hf["hidden_size"],
                   n_heads=hf["num_attention_heads"],
                   n_kv=hf["num_key_value_heads"], head_dim=hf["head_dim"],
                   d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
                   layers=hf["num_hidden_layers"])


def make_engine(cfg, params, eng: dict):
    from repro.core.backend import ArrayBackend
    from repro.core.compile_cache import CompileCache
    from repro.serve.engine import PagedServeEngine
    from repro.serve.scheduler import AdmissionScheduler
    return PagedServeEngine(
        cfg, params, slots=int(eng["slots"]), page_size=int(eng["page_size"]),
        pages_per_slot=int(eng["pages_per_slot"]),
        pool_pages=int(eng["pool_pages"]),
        backend=ArrayBackend(cache=CompileCache()),
        scheduler=AdmissionScheduler(), kernel=eng["kernel"])


def warm_up(engine, traffic: dict, vocab: int, seed: int) -> None:
    """One short request per prompt bucket the traffic reaches: compiles
    or loads, and runs, every prefill shape and the decode step."""
    from repro.serve.engine import Request
    rng = np.random.default_rng([seed, 3])
    cap = engine.pool.vcap
    for i, b in enumerate(gen.prompt_buckets(traffic, cap)):
        n = min(b, int(traffic["prompt"]["max"]))
        req = Request(rid=-1 - i, prompt=rng.integers(0, vocab, n),
                      max_new=2)
        engine.run([req])
        if not req.done:
            raise RuntimeError(f"warm-up request of {n} tokens unfinished")


class _Work:
    """Model operations and paged-attention work of what the engine ran,
    counted from live lengths (traced windows only)."""

    def __init__(self, model: DenseLM):
        self.m = model
        self.prefill_flops = self.decode_flops = 0
        self.attn_min_s = 0.0

    def iteration(self, before: dict, live: List, peaks: dict) -> None:
        from harness.work import paged_attention, roofline_s
        m = self.m
        ctx = []
        for r in live:
            n0, first0 = before[id(r)]
            n1 = len(r.out)
            S = len(r.prompt)
            if first0 and r.t_first is not None:     # admitted: prefilled
                self.prefill_flops += m.prefill_flops(S)
            for j in range(max(n0, 1), n1):          # decoded out[j]
                ctx.append(S + j)
        if not ctx:
            return
        self.decode_flops += m.decode_flops(ctx)
        f, b = paged_attention([1] * len(ctx), ctx, m.n_heads, m.n_kv,
                               m.head_dim)
        self.attn_min_s += roofline_s(f, b, peaks)[0] * m.layers


def build(cell, seed: int):
    """Set-up: the program's model, the seeded weights and the engine."""
    import jax
    from repro.models.lm import lm_init
    hf = cell.config
    ref = cell.reference()
    cfg = model_config(hf, hf["program"])
    params = ref.make_weights(seed, hf)
    want = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError("reference weights do not match the program's "
                         "parameter layout")
    engine = make_engine(cfg, params, hf["engine"])
    warm_up(engine, cell.traffic, cfg.vocab, seed)
    return cfg, params, engine


def drive(engine, arrivals, ctx, seconds: float, drain_s: float,
          work: "_Work | None" = None):
    """Release ``arrivals`` at their due times for ``seconds`` and serve
    what is due to the end (at most ``drain_s`` past the window). Returns
    (requests, due times, the window's engine counters)."""
    from repro.serve.engine import Request
    reqs = [Request(rid=i, prompt=a.prompt, max_new=a.max_new)
            for i, a in enumerate(arrivals)]
    cache = engine.backend.cache
    t0 = ctx.begin_window()
    t_end = t0 + seconds
    due = [t0 + a.due_s for a in arrivals]
    keys = ("steps", "decoded", "prefill_dispatches", "prefill_rows")
    s0 = {k: engine.stats[k] for k in keys}
    c0 = cache.stats["compile_s"]
    window = None
    released = 0
    open_reqs: List = []
    while True:
        now = time.perf_counter()
        while released < len(reqs) and due[released] <= now:
            engine.scheduler.enqueue(reqs[released], now=due[released])
            open_reqs.append(reqs[released])
            released += 1
        if window is None and now >= t_end:
            ctx.end_window()
            window = {k: engine.stats[k] - s0[k] for k in keys}
            window["compile_s"] = cache.stats["compile_s"] - c0
            window["backlog"] = engine.scheduler.pending()
        open_reqs = [r for r in open_reqs if not r.done]
        busy = engine.scheduler.has_pending() or any(
            a is not None for a in engine.active)
        if not busy:
            if released >= len(reqs) and window is not None:
                break
            with ctx.annotate("wait_arrival"):
                nxt = due[released] if released < len(reqs) else t_end
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        if now > t_end + drain_s:
            break
        before = {id(r): (len(r.out), r.t_first is None) for r in open_reqs}
        with ctx.annotate("engine_step"):
            engine.run([], max_steps=engine.stats["steps"] + 1)
        if work is not None and window is None:
            work.iteration(before, open_reqs, ctx.peaks)
    window["drain_s"] = time.perf_counter() - t_end
    return reqs, due, window


def records(reqs, due) -> List[dict]:
    out = []
    for r, d in zip(reqs, due):
        ok = r.done and r.finish_reason == "length" and \
            len(r.out) == r.max_new
        n = len(r.out)
        out.append({
            "ok": ok, "n_out": n, "prompt": len(r.prompt),
            "ttft_s": (r.t_first - d) if ok else None,
            "tpot_s": ((r.t_done - r.t_first) / (n - 1)
                       if ok and n > 1 else None)})
    return out


def check_sample(reqs, count: int, seed: int):
    """(prompt, served) of the requests the check reads."""
    done = [r for r in reqs if r.done and len(r.out) == r.max_new]
    return [(np.asarray(r.prompt), np.asarray(r.out))
            for r in pick_sample(done, count, seed)]


def run(cell, ctx) -> dict:
    hf, traffic = cell.config, cell.traffic
    ref = cell.reference()
    cfg, params, engine = build(cell, ctx.seed)
    arrivals = gen.schedule(traffic, ctx.seconds, ctx.seed, cfg.vocab)
    work = _Work(dense_lm(hf)) if ctx.trace else None
    drain_s = float(traffic["drain_s"])
    reqs, due, window = drive(engine, arrivals, ctx, ctx.seconds, drain_s,
                              work)
    ctx.read_memory_peak()
    recs = records(reqs, due)
    failed = sum(not x["ok"] for x in recs)

    # -- check: after the window, the program's state freed ---------------
    seqs = check_sample(reqs, int(hf["check"]["requests"]), ctx.seed)
    del engine
    gc.collect()
    gaps = ref.control_gaps if ctx.control else ref.served_gaps
    gap = float(max(gaps(params, hf, seqs).max(), 0.0)) \
        if seqs else float("inf")
    del params
    gc.collect()
    obs = {"requests": recs, "window": window,
           "slots": int(hf["engine"]["slots"]), "drain_s": drain_s,
           "work": None if work is None else {
               k: v for k, v in vars(work).items() if k != "m"}}
    return {"obs": obs, "attempted": len(recs), "failed": failed,
            "checks": {"logit_gap": (gap, hf["limits"]["logit_gap"]),
                       "requests_unfinished": (failed, 0)}}


def pick_sample(done: List, count: int, seed: int) -> List:
    """The longest finished request and a seeded draw of the others."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 5])
    k = min(count - 1, len(rest))
    pick = [rest[i] for i in sorted(rng.choice(len(rest), k, replace=False))]
    return [longest] + pick
