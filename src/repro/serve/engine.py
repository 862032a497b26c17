"""Serving engines: continuous decode over a request pool through the
shared AOT ``CompileCache`` (compile-once/serve-many — the serving face of
the paper's array-launch amortization).

Two engines share one driver (``_EngineBase.run``: admit -> grow -> step):

``ServeEngine``      the fixed-partition baseline: every slot owns a
                     private ``capacity``-row KV ring and admission
                     prefills ONE slot per dispatch.
``PagedServeEngine`` the paged subsystem: one shared page pool
                     (``repro.serve.kv_pool``) backs every slot through
                     per-slot page tables; admission packs a whole
                     priority-ordered group of waiting prompts into ONE
                     length-bucketed prefill executable; pages are
                     allocated a page at a time as requests decode and
                     batch-class requests are preempted (pages freed,
                     request requeued) when interactive work needs the
                     pool or the slots.

The paged engine additionally owns two optimizations this module only
orchestrates (the mechanisms live in ``kv_pool`` and ``models.lm``):

* ``kernel=`` selects the compiled attention data path. ``"gather"``
  materializes each slot's dense KV view per step (XLA gathers — the
  bitwise-stable baseline); ``"pallas"`` walks the page table inside
  ``kernels.paged_attention`` so the dense view is never built;
  ``"auto"`` picks pallas on TPU, gather elsewhere (interpret-mode
  Pallas is correct but slow). The choice is baked into every decode /
  prefill executable (it is part of the AOT cache key), never branched
  at runtime.
* prefix sharing (copy-on-write). After a prompt prefills, its pages
  are REGISTERED under a digest of the prompt tokens, which pins them
  in the pool past the request's lifetime. A later prompt that starts
  with a registered prefix is admitted WARM: it maps the pinned pages
  into its own table (refcount++, zero KV written) and prefills only
  its suffix, continuing from the divergence point — TTFT approaches a
  single decode step for a fully-warm prompt. Shared pages are
  immutable: any write landing in one — the suffix's first page when
  divergence is mid-page, or the original owner decoding past a
  registered boundary — first breaks the page out via
  ``PagePool.cow_page`` + ``models.lm.paged_copy`` (one page copy),
  so readers of the pinned prefix never observe another request's
  tokens. Pinned prefixes are evicted LRU under allocation pressure
  (cheaper than preempting live work), and a page is cleared + reused
  only when its LAST reference (tables and registry both) drops.

Both engines guard KV overflow at admission: a prompt that cannot fit is
rejected outright, and a generation budget is clamped so decode can never
silently wrap the ring past live history (``finish_reason="capacity"``).
Neither engine owns jit plumbing: the decode step and every prefill
signature are AOT-compiled through a ``LaunchBackend``'s shared persistent
``CompileCache`` — the same cache the launcher uses — so a process (or a
*later* process) that already launched this model serves its first token
without paying trace+compile again, and vice versa.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import ArrayBackend
from repro.core.telemetry import RequestRecord, class_summary, slo_attainment
from repro.kernels.ops import on_tpu
from repro.obs import flight as _flight
from repro.obs import metrics as _obs
from repro.obs.trace import TRACER
from repro.models.lm import (cache_init, decode_step, paged_cache_init,
                             paged_clear, paged_copy, paged_decode_step,
                             paged_prefill, prefill)
from repro.models.spec import ModelConfig
from repro.serve.kv_pool import PagePool
from repro.serve.scheduler import AdmissionScheduler, bucket_len


@dataclass(eq=False)                      # identity semantics: a request is
class Request:                            # a ticket, not a value
    rid: int
    prompt: np.ndarray                    # (S,)
    max_new: int
    priority: str = "interactive"         # "interactive" | "batch"
    out: List[int] = field(default_factory=list)
    done: bool = False
    finish_reason: Optional[str] = None
    # telemetry stamps (perf_counter seconds); budget = max_new after the
    # capacity clamp. Reset by preemption: a preempted request restarts.
    # t_admit: the request took a slot, before its prefill was dispatched
    t_enqueue: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    preemptions: int = 0
    budget: Optional[int] = None

    def record(self) -> RequestRecord:
        n = len(self.out)
        ttft = (self.t_first - self.t_enqueue) if self.t_first else 0.0
        tpot = ((self.t_done - self.t_first) / (n - 1)
                if n > 1 and self.t_done and self.t_first else 0.0)
        queue = (self.t_admit - self.t_enqueue
                 if self.t_admit is not None else 0.0)
        return RequestRecord(rid=self.rid, priority=self.priority,
                             ttft_s=ttft, tpot_s=tpot, n_tokens=n,
                             preemptions=self.preemptions,
                             finish=self.finish_reason or "length",
                             queue_s=queue)


class _EngineBase:
    """Shared driver: scheduler-ordered admission, batched decode,
    capacity guards, per-request/per-class telemetry."""

    def __init__(self, cfg: ModelConfig, params, slots: int,
                 backend: Optional[ArrayBackend],
                 scheduler: Optional[AdmissionScheduler]):
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.backend = backend if backend is not None else ArrayBackend()
        self.scheduler = scheduler if scheduler is not None \
            else AdmissionScheduler()
        # called as logit_sink(request, logits_row) for every row of logits
        # a request gets (its prefill, then each decode step): lets a
        # caller check one attention path against another on live traffic.
        # Rows are sliced off the device only while a sink is set.
        self.logit_sink: Optional[Callable[[Request, jax.Array], None]] = None
        self.tokens = jnp.zeros((slots, 1), jnp.int32)
        self.pos = jnp.zeros((slots, 1), jnp.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self._stalled: set = set()        # slots waiting on a page
        self.records: List[RequestRecord] = []
        self.stats = {"decoded": 0, "admitted": 0, "steps": 0,
                      "rejected_over_capacity": 0, "capacity_clamped": 0,
                      "preemptions": 0, "pool_exhausted": 0,
                      "stall_steps": 0, "prefill_dispatches": 0,
                      "compile_sources": {}}
        # registry instruments (created once; observed only while enabled)
        self._m_ttft = _obs.histogram("serve.ttft_s")
        self._m_tpot = _obs.histogram("serve.tpot_s")
        self._m_preempt = _obs.counter("serve.preemptions")
        self._m_occupancy = _obs.gauge("serve.pool_occupancy")

    # -- capacity guard ----------------------------------------------------
    def _request_capacity(self) -> int:
        raise NotImplementedError

    def _screen(self, req: Request) -> bool:
        """Admission guard: reject a prompt that cannot fit; clamp the
        generation budget so decode never wraps the ring past live
        history. Prompt rows occupy [0, S); generated token t is written
        at S + t - 1 when fed back, and the last token is never fed, so
        S + budget - 1 <= capacity."""
        cap = self._request_capacity()
        S = len(req.prompt)
        allowed = cap - S + 1
        if S > cap or allowed <= 0:
            req.done = True
            req.finish_reason = "rejected_over_capacity"
            req.t_done = time.perf_counter()
            self.stats["rejected_over_capacity"] += 1
            self.records.append(req.record())
            return False
        if req.max_new > allowed:
            if req.budget is None:           # count once per request
                self.stats["capacity_clamped"] += 1
            req.budget = allowed
        else:
            req.budget = req.max_new
        req.done = False
        req.finish_reason = None
        return True

    # -- per-engine hooks --------------------------------------------------
    def _admit(self) -> int:
        """Admit from the scheduler into free slots; returns #admitted."""
        raise NotImplementedError

    def _pre_step(self) -> None:
        """Hook before a decode step (page growth for the paged engine)."""

    def _step_executable(self) -> Tuple[np.ndarray, jax.Array]:
        """Run one decode step -> (next token per slot, logits)."""
        raise NotImplementedError

    def _emit(self, req: Request, logits: jax.Array, *index: int) -> None:
        if self.logit_sink is not None:
            self.logit_sink(req, logits[index])

    def _release_slot(self, i: int) -> None:
        self.active[i] = None

    # -- shared decode bookkeeping ----------------------------------------
    def _finish(self, i: int, reason: Optional[str] = None) -> None:
        req = self.active[i]
        req.done = True
        req.finish_reason = reason or req.finish_reason or (
            "length" if req.budget == req.max_new else "capacity")
        req.t_done = time.perf_counter()
        rec = req.record()
        self.records.append(rec)
        if _obs.REGISTRY.enabled and rec.n_tokens > 0:
            self._m_ttft.observe(rec.ttft_s)
            self._m_tpot.observe(rec.tpot_s)
            _obs.REGISTRY.series_append("serve.ttft_s", time.time(),
                                        rec.ttft_s)
        self._release_slot(i)

    def step(self) -> None:
        """One batched decode step across all slots."""
        with TRACER.span("serve.decode"):
            nxt, logits = self._step_executable()
        with TRACER.span("serve.emit"):
            now = time.perf_counter()
            self.stats["steps"] += 1
            for i, req in enumerate(self.active):
                if req is None or i in self._stalled:
                    continue
                self._emit(req, logits, i, 0)
                req.out.append(int(nxt[i]))
                self.stats["decoded"] += 1
                if req.t_first is None:
                    req.t_first = now
                if len(req.out) >= req.budget:
                    self._finish(i)

    def run(self, requests: List[Request], max_steps: int = 10_000) -> dict:
        with TRACER.span("serve.run"):
            return self._run(requests, max_steps)

    def _run(self, requests: List[Request], max_steps: int) -> dict:
        t0 = time.perf_counter()
        for r in requests:
            self.scheduler.enqueue(r)
        while ((self.scheduler.has_pending()
                or any(a is not None for a in self.active))
               and self.stats["steps"] < max_steps):
            with TRACER.span("serve.admit"):
                admitted = self._admit()
            if any(a is not None for a in self.active):
                with TRACER.span("serve.pre_step"):
                    self._pre_step()
                self.step()
            elif not admitted and self.scheduler.has_pending():
                # idle engine that cannot place the head request: fail it
                # loudly instead of spinning (pool smaller than one prompt)
                req = self.scheduler.pop_next()
                req.done, req.finish_reason = True, "pool_exhausted"
                req.t_done = time.perf_counter()
                self.stats["pool_exhausted"] += 1
                self.records.append(req.record())
        self.stats["wall_s"] = time.perf_counter() - t0
        self.stats["classes"] = class_summary(self.records)
        slo = self.scheduler.target_first_result_s
        if slo is not None:
            att = slo_attainment(self.records, slo)
            self.stats["slo_attainment"] = att
            if att < _flight.RECORDER.slo_min:
                _flight.RECORDER.trigger("slo_breach", attainment=att,
                                         target_first_result_s=slo)
        return self.stats


# ----------------------------------------------------------------------
# Fixed-partition baseline
# ----------------------------------------------------------------------

class ServeEngine(_EngineBase):
    """Fixed-slot batched decoder: every slot owns a private KV ring of
    ``capacity`` rows (static partition), admission prefills one slot per
    dispatch (the paper's serial-launch analogue at the serving layer)."""

    def __init__(self, cfg: ModelConfig, params, slots: int = 8,
                 capacity: int = 256,
                 backend: Optional[ArrayBackend] = None,
                 scheduler: Optional[AdmissionScheduler] = None):
        super().__init__(cfg, params, slots, backend, scheduler)
        self.capacity = capacity
        self.caches = cache_init(cfg, slots, capacity)

        def step_fn(p, c, t, po):
            return decode_step(p, c, t, po, cfg)

        self._step, src = self.backend.compile(
            step_fn, (params, self.caches, self.tokens, self.pos),
            extras=("serve-step", cfg.name, slots, capacity))
        self.stats["compile_sources"]["step"] = src
        self._prefill_by_len: dict = {}   # prompt length -> AOT executable

    def _request_capacity(self) -> int:
        return self.capacity

    def _prefill(self, tokens):
        """AOT prefill, one executable per prompt length, shared-cache."""
        compiled = self._prefill_by_len.get(tokens.shape)
        if compiled is None:
            cfg, capacity = self.cfg, self.capacity

            def prefill_fn(p, t):
                return prefill(p, {"tokens": t}, cfg, capacity=capacity)

            compiled, src = self.backend.compile(
                prefill_fn, (self.params, tokens),
                extras=("serve-prefill", cfg.name, capacity))
            self._prefill_by_len[tokens.shape] = compiled
            self.stats["compile_sources"][f"prefill_s{tokens.shape[1]}"] = src
        return compiled(self.params, tokens)

    def admit(self, req: Request) -> bool:
        """Prefill a request into a free slot (one-slot batch prefill)."""
        if req.budget is None and not self._screen(req):
            return False                      # rejected: over capacity
        for i, a in enumerate(self.active):
            if a is None:
                req.t_admit = time.perf_counter()
                logits, caches = self._prefill(
                    jnp.asarray(req.prompt, jnp.int32)[None])
                self.stats["prefill_dispatches"] += 1
                # write slot i of every cache leaf
                def put(dst, src):
                    return jax.lax.dynamic_update_index_in_dim(
                        dst, src[0], i, 0)
                # cache leaves carry the slot axis at position 1 (axis 0 is
                # the scan-stack axis)
                self.caches = jax.tree_util.tree_map(
                    lambda d, s: jax.vmap(put)(d, s), self.caches, caches)
                self._emit(req, logits, 0, -1)
                tok = int(jnp.argmax(logits[0, -1]))
                req.out.append(tok)
                req.t_first = time.perf_counter()
                self.tokens = self.tokens.at[i, 0].set(tok)
                self.pos = self.pos.at[i, 0].set(len(req.prompt))
                self.active[i] = req
                self.stats["admitted"] += 1
                if len(req.out) >= req.budget:
                    self._finish(i)
                return True
        return False

    def _admit(self) -> int:
        n = 0
        while self.scheduler.has_pending():
            head = self.scheduler.peek_next()
            if not self._screen(head):
                self.scheduler.pop_next()
                continue
            if not self.admit(head):
                break
            self.scheduler.pop_next()
            n += 1
        return n

    def _step_executable(self):
        logits, self.caches = self._step(self.params, self.caches,
                                         self.tokens, self.pos)
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        self.tokens = nxt[:, None]
        self.pos = self.pos + 1
        return np.asarray(nxt), logits


# ----------------------------------------------------------------------
# Paged engine: shared pool, batched prefill, priority preemption
# ----------------------------------------------------------------------

class PagedServeEngine(_EngineBase):
    """Continuous-batching decoder over one shared KV page pool.

    * capacity is POOLED: ``pool_pages`` pages back all ``slots`` slots;
      a slot holds at most ``pages_per_slot`` pages (its virtual capacity
      ``vcap = pages_per_slot * page_size`` rows), allocated one page at a
      time as its request decodes — short requests never reserve long-
      request memory, so ``pool_pages`` can be far below
      ``slots * pages_per_slot`` (oversubscription);
    * admission pops a priority-ordered GROUP of same-bucket prompts and
      prefills them in ONE padded executable (``batched_prefill=False``
      reverts to the exact-shape one-slot loop — the A/B in ``fig_serve``);
    * when the pool or the slots are exhausted, batch-class requests are
      preempted for interactive ones (youngest victim first; pages freed,
      victim requeued at the front of its class and restarted on
      re-admission), with admission-time preemption gated by the
      scheduler's ``target_first_result_s`` SLO; a request that can't
      grow and has no victim STALLS until peers free pages, a full-pool
      deadlock preempts one victim to unblock the rest, and only a lone
      request larger than the entire pool is finished early
      (``finish_reason="pool_exhausted"``).

    Token output with ``kernel="gather"`` is bit-identical to
    ``ServeEngine`` on the same trace (same prompts, same admission
    shapes): the compiled step gathers each slot's pages into exactly the
    dense view ``decode_step`` always ran on. ``kernel="pallas"`` keeps
    the same math (online softmax over the same masked rows) without ever
    materializing that view. The two paths sum the softmax in different
    orders, so logits agree within a few bf16 ulps, not bit for bit, and
    greedy tokens may part where two logits tie within that
    (``tests/test_paged_attention.py``, ``chip_smoke.py``).
    """

    def __init__(self, cfg: ModelConfig, params, slots: int = 8,
                 page_size: int = 16, pages_per_slot: int = 8,
                 pool_pages: Optional[int] = None,
                 backend: Optional[ArrayBackend] = None,
                 scheduler: Optional[AdmissionScheduler] = None,
                 batched_prefill: bool = True,
                 kernel: str = "auto",
                 prefix_sharing: bool = False,
                 prefix_min_tokens: Optional[int] = None):
        super().__init__(cfg, params, slots, backend, scheduler)
        if pool_pages is None:
            pool_pages = slots * pages_per_slot
        self.pool = PagePool(pool_pages, page_size, slots, pages_per_slot)
        self.kv = paged_cache_init(cfg, slots, pool_pages, page_size)
        self._tables_host = self.pool.table_array()
        self.tables = jnp.asarray(self._tables_host)
        self._tables_dirty = False
        self.batched_prefill = batched_prefill
        if kernel == "auto":
            kernel = "pallas" if on_tpu() else "gather"
        if kernel not in ("gather", "pallas"):
            raise ValueError(f"kernel must be gather|pallas|auto: {kernel!r}")
        self.kernel = kernel
        # right-padded batched prefill is unsound for SSM state (the
        # recurrence would absorb pad tokens): group by exact length then
        self._pad_safe = not any(b.ssm is not None
                                 for g in cfg.groups for b in g.pattern)
        # prefix sharing caches attention pages only; an SSM config's
        # recurrent state at the divergence point is NOT in the pool, so a
        # warm continuation would decode from a wrong (zero) state
        self._prefix_ok = prefix_sharing and self._pad_safe
        self.prefix_min_tokens = (page_size if prefix_min_tokens is None
                                  else prefix_min_tokens)
        self._admit_order = 0                  # preemption recency clock
        self._admit_seq: List[int] = [0] * slots
        self._dense_view_bytes, self._kv_row_bytes = self._kv_geometry()

        def step_fn(p, kv, tables, t, po, live):
            return paged_decode_step(p, kv, tables, t, po, cfg, live=live,
                                     kernel=kernel)

        self._live = jnp.ones((slots,), bool)
        self._step, src = self.backend.compile(
            step_fn, (params, self.kv, self.tables, self.tokens, self.pos,
                      self._live),
            extras=("serve-paged-step", cfg.name, slots, pool_pages,
                    page_size, pages_per_slot, kernel))
        self.stats["compile_sources"]["step"] = src
        self._prefill_by_shape: dict = {}      # (B, S) -> AOT executable
        self._warm_by_len: dict = {}           # S_pad  -> AOT executable
        self.stats.update({"prefix_hits": 0, "prefix_misses": 0,
                           "prefix_registered": 0, "cow_pages": 0,
                           "prefill_rows": 0, "kv_bytes_avoided": 0,
                           "kernel_pages_read": 0,
                           "kernel_pages_skipped": 0})
        self._m_phit = _obs.counter("serve.prefix.hits")
        self._m_pmiss = _obs.counter("serve.prefix.misses")
        self._m_bytes = _obs.counter("serve.kernel.bytes_avoided")
        self._m_read = _obs.counter("serve.kernel.pages_read")
        self._m_skip = _obs.counter("serve.kernel.pages_skipped")

    def _kv_geometry(self) -> Tuple[int, int]:
        """(bytes of dense per-slot views the gather path materializes per
        decode step, bytes one KV cache row costs across all layers)."""
        dense = row = 0
        vcap = self.pool.vcap
        for gtree in self.kv:
            for btree in gtree.values():
                sub = btree.get("attn")
                if not sub:
                    continue
                for name, leaf in sub.items():
                    R = leaf.shape[0]
                    # per-row elements: page leaves are (R, P, ps, ...) or
                    # head-major (R, P, K, ps, ...)
                    tail = int(np.prod(leaf.shape[2:])) // self.pool.page_size
                    item = np.dtype(leaf.dtype).itemsize
                    dense += R * self.slots * vcap * tail * item
                    if name != "pos":
                        row += R * tail * item
        return dense, row

    def _request_capacity(self) -> int:
        return self.pool.vcap

    # -- prefill executables ----------------------------------------------
    def _prefill_exec(self, B: int, S: int):
        compiled = self._prefill_by_shape.get((B, S))
        if compiled is None:
            cfg, kern = self.cfg, self.kernel

            def prefill_fn(p, kv, trows, toks, lens, sids):
                return paged_prefill(p, kv, trows, toks, lens, sids, cfg,
                                     kernel=kern)

            example = (self.params, self.kv,
                       jnp.zeros((B, self.pool.pages_per_slot), jnp.int32),
                       jnp.zeros((B, S), jnp.int32),
                       jnp.zeros((B,), jnp.int32),
                       jnp.zeros((B,), jnp.int32))
            compiled, src = self.backend.compile(
                prefill_fn, example,
                extras=("serve-paged-prefill", cfg.name, self.pool.n_pages,
                        self.pool.page_size, self.pool.pages_per_slot, kern))
            self._prefill_by_shape[(B, S)] = compiled
            self.stats["compile_sources"][f"prefill_b{B}_s{S}"] = src
        return compiled

    def _warm_exec(self, S: int):
        """Suffix-continuation prefill (B=1): rows start at ``starts`` and
        attend the slot's already-resident prefix pages through the table."""
        compiled = self._warm_by_len.get(S)
        if compiled is None:
            cfg, kern = self.cfg, self.kernel

            def warm_fn(p, kv, trows, toks, lens, sids, starts):
                return paged_prefill(p, kv, trows, toks, lens, sids, cfg,
                                     starts=starts, kernel=kern)

            example = (self.params, self.kv,
                       jnp.zeros((1, self.pool.pages_per_slot), jnp.int32),
                       jnp.zeros((1, S), jnp.int32),
                       jnp.zeros((1,), jnp.int32),
                       jnp.zeros((1,), jnp.int32),
                       jnp.zeros((1,), jnp.int32))
            compiled, src = self.backend.compile(
                warm_fn, example,
                extras=("serve-paged-warm", cfg.name, self.pool.n_pages,
                        self.pool.page_size, self.pool.pages_per_slot, kern))
            self._warm_by_len[S] = compiled
            self.stats["compile_sources"][f"warm_s{S}"] = src
        return compiled

    # -- prefix sharing ----------------------------------------------------
    @staticmethod
    def _digest(tokens) -> bytes:
        return hashlib.sha1(
            np.ascontiguousarray(tokens, np.int32).tobytes()).digest()

    def _match_prefix(self, req: Request):
        """Longest registered, token-verified prefix strictly shorter than
        or equal to the prompt: returns (L, entry) or None. L == len(prompt)
        still re-prefills the last token (logits need a forward pass)."""
        if not self._prefix_ok:
            return None
        S = len(req.prompt)
        for L in self.pool.prefix_lengths():
            if L > S or L < self.prefix_min_tokens:
                continue
            e = self.pool.lookup_prefix(self._digest(req.prompt[:L]),
                                        req.prompt)
            if e is not None:
                return L, e
        return None

    def _cow(self, slot: int, pg_idx: int, priority: str) -> bool:
        """Break the shared page at ``slot``'s table index ``pg_idx`` out
        into a private copy (pool bookkeeping + device-side page copy)."""
        res = self.pool.cow_page(slot, pg_idx)
        if res is None and self._ensure_pages(1, priority, exclude=slot):
            res = self.pool.cow_page(slot, pg_idx)
        if res is None:
            return False
        src, dst = res
        self.kv = paged_copy(self.kv, src, dst)
        self.stats["cow_pages"] += 1
        self._tables_dirty = True
        return True

    def _register(self, slot: int, req: Request) -> None:
        """Pin the pages holding ``req``'s full prompt under its digest.
        The boundary page may later take the owner's decode writes — the
        owner COWs it first (``_pre_step``), leaving the pinned snapshot
        frozen."""
        if not self._prefix_ok:
            return
        S = len(req.prompt)
        if S < self.prefix_min_tokens:
            return
        pages = self.pool.pages_of(slot)[: self.pool.pages_for_tokens(S)]
        if self.pool.register_prefix(self._digest(req.prompt),
                                     req.prompt, pages):
            self.stats["prefix_registered"] += 1

    # -- preemption --------------------------------------------------------
    def _preempt(self, i: int) -> None:
        """Evict slot ``i``'s (batch-class) request: free + clear its
        pages, requeue it at the front of its class, restart-on-readmit."""
        req = self.active[i]
        req.out.clear()
        req.t_admit = req.t_first = None
        req.preemptions += 1
        self.stats["preemptions"] += 1
        if _obs.REGISTRY.enabled:
            self._m_preempt.inc()
        self.scheduler.requeue_front(req)
        self._release_slot(i)

    def _pick_victim(self, exclude: Optional[int] = None) -> Optional[int]:
        """Youngest-admitted preemptible (batch-class) active slot: the
        least sunk work is thrown away, and FIFO order within the batch
        class is preserved on requeue."""
        best = None
        for i, req in enumerate(self.active):
            if req is None or i == exclude:
                continue
            if req.priority not in self.scheduler.preemptible:
                continue
            if best is None or self._admit_seq[i] > self._admit_seq[best]:
                best = i
        return best

    def _ensure_pages(self, need: int, priority: str,
                      exclude: Optional[int] = None,
                      admission: bool = False) -> bool:
        """Make ``need`` pages available, preempting batch-class work when
        the requester is interactive. Admission-time preemption is gated
        by the scheduler's TTFT SLO (batch keeps its slots while the queue
        wait is comfortably inside the target); an already-RUNNING
        interactive request growing a page always may preempt — stalling
        it would burn its TPOT for nothing. Before touching live work,
        cold pinned prefixes are evicted LRU — cache, not computation, so
        ANY priority may reclaim them."""
        if self.pool.free_pages < need:
            freed = self.pool.evict_prefixes(need)
            if freed:
                self.kv = paged_clear(self.kv, freed)
        while self.pool.free_pages < need:
            if priority != "interactive":
                return False
            if (admission
                    and self.scheduler.target_first_result_s is not None
                    and not self.scheduler.should_preempt()):
                return False
            victim = self._pick_victim(exclude=exclude)
            if victim is None:
                return False
            self._preempt(victim)
        return True

    def _release_slot(self, i: int) -> None:
        freed = self.pool.free_slot(i)
        if freed:
            self.kv = paged_clear(self.kv, freed)
            self._tables_dirty = True
        self.active[i] = None

    # -- admission ---------------------------------------------------------
    def _bucket(self, n: int) -> int:
        if not self._pad_safe:
            return n                          # exact-length groups (SSM)
        return min(bucket_len(n), self.pool.vcap)

    def _admit(self) -> int:
        if self._stalled:
            # page-starved: admitting more work would steal the pages the
            # stalled slots are waiting for
            return 0
        # slot pressure: an overdue interactive head may evict a batch slot
        if (all(a is not None for a in self.active)
                and self.scheduler.pending("interactive")
                and self.scheduler.should_preempt()):
            victim = self._pick_victim()
            if victim is not None:
                self._preempt(victim)
        free = [i for i, a in enumerate(self.active) if a is None]
        if not free:
            return 0
        # screen the head until it is admittable (pop rejects outright)
        while self.scheduler.has_pending():
            head = self.scheduler.peek_next()
            if self._screen(head):
                break
            self.scheduler.pop_next()
        if not self.scheduler.has_pending():
            return 0
        head = self.scheduler.peek_next()
        m = self._match_prefix(head)
        if m is not None:
            L, entry = m
            if self._admit_warm(head, L, entry):
                self.scheduler.pop_next()
                return 1
            # warm admission couldn't get pages/slot: fall through cold
        if not self._ensure_pages(
                self.pool.pages_for_tokens(len(head.prompt)), head.priority,
                admission=True):
            return 0
        free = [i for i, a in enumerate(self.active) if a is None]
        if self.batched_prefill:
            b = self._bucket(len(head.prompt))
            group = self.scheduler.pop_group(
                len(free), match=lambda r: self._bucket(len(r.prompt)) == b)
        else:
            group = [self.scheduler.pop_next()]
        placed: List[Tuple[int, Request]] = []
        leftover: List[Request] = []
        for req in group:
            if not self._screen(req):
                continue                     # rejected + recorded in _screen
            need = self.pool.pages_for_tokens(len(req.prompt))
            free = [i for i, a in enumerate(self.active) if a is None
                    and all(i != s for s, _ in placed)]
            if not free or not self._ensure_pages(need, req.priority,
                                                  admission=True):
                leftover.append(req)
                continue
            slot = free.pop(0)
            self.pool.alloc(slot, need)
            placed.append((slot, req))
        for req in reversed(leftover):       # restore original queue order
            self.scheduler.requeue_front(req)
        if placed:
            with TRACER.span("serve.prefill"):
                self._prefill_commit(placed)
        return len(placed)

    def _admit_warm(self, req: Request, L: int, entry: dict) -> bool:
        """Admit ``req`` onto a registered prefix: map the pinned pages
        into a free slot (refcount++, zero KV written), claim private
        pages for the suffix, COW the boundary page when the divergence
        point is inside a shared page, then prefill ONLY the suffix
        (continuing from absolute position ``suffix_start``). A fully-
        cached prompt re-runs just its last token to produce logits."""
        free = [i for i, a in enumerate(self.active) if a is None]
        if not free:
            return False
        slot = free[0]
        S = len(req.prompt)
        shared = entry["pages"]
        n_priv = self.pool.pages_for_tokens(S) - len(shared)
        if not self.pool.share(slot, shared):
            return False
        ok = n_priv <= 0 or (
            self._ensure_pages(n_priv, req.priority, admission=True)
            and self.pool.alloc(slot, n_priv) is not None)
        suffix_start = min(L, S - 1)
        pg_w = suffix_start // self.pool.page_size
        if ok and pg_w < len(shared):
            ok = self._cow(slot, pg_w, req.priority)
        if not ok:
            freed = self.pool.free_slot(slot)   # undo the share
            if freed:
                self.kv = paged_clear(self.kv, freed)
            return False
        req.t_admit = time.perf_counter()
        with TRACER.span("serve.prefill"):
            S_suf = S - suffix_start
            S_pad = min(bucket_len(S_suf), self.pool.vcap)
            toks = np.zeros((1, S_pad), np.int64)
            toks[0, :S_suf] = req.prompt[suffix_start:]
            trows = self.pool.table_array()[slot][None]
            exe = self._warm_exec(S_pad)
            logits, self.kv = exe(self.params, self.kv,
                                  jnp.asarray(trows, jnp.int32),
                                  jnp.asarray(toks, jnp.int32),
                                  jnp.asarray([S_suf], jnp.int32),
                                  jnp.asarray([slot], jnp.int32),
                                  jnp.asarray([suffix_start], jnp.int32))
            self.stats["prefill_dispatches"] += 1
            self.stats["prefill_rows"] += S_suf
            self.stats["prefix_hits"] += 1
            if _obs.REGISTRY.enabled:
                self._m_phit.inc()
            self._emit(req, logits, 0, -1)
            tok = int(jnp.argmax(logits[0, -1]))
            req.out.append(tok)
            req.t_first = time.perf_counter()
            self.tokens = self.tokens.at[slot, 0].set(tok)
            self.pos = self.pos.at[slot, 0].set(S)
            self.active[slot] = req
            self._admit_order += 1
            self._admit_seq[slot] = self._admit_order
            self.stats["admitted"] += 1
            self._tables_dirty = True
            self._register(slot, req)  # a warm prompt seeds longer ones too
            if len(req.out) >= req.budget:
                self._finish(slot)
        return True

    def _prefill_commit(self, placed: List[Tuple[int, Request]]) -> None:
        """One prefill dispatch for the whole group. In batched mode the
        executable has a fixed batch of ``slots`` rows — absent slots ride
        as dummy rows whose table is -1 and slot id out of range, so every
        one of their writes is dropped by the scatter."""
        if self.batched_prefill:
            S = max(self._bucket(len(r.prompt)) for _, r in placed)
            B = self.slots
        else:
            S = len(placed[0][1].prompt)     # exact shape, no padding
            B = 1
        toks = np.zeros((B, S), np.int64)
        lens = np.zeros((B,), np.int64)
        trows = np.full((B, self.pool.pages_per_slot), -1, np.int32)
        sids = np.full((B,), self.slots, np.int64)  # OOB = dummy row
        table = self.pool.table_array()
        t_admit = time.perf_counter()
        for r, (slot, req) in enumerate(placed):
            req.t_admit = t_admit
            n = len(req.prompt)
            toks[r, :n] = req.prompt
            lens[r] = n
            trows[r] = table[slot]
            sids[r] = slot
        exe = self._prefill_exec(B, S)
        logits, self.kv = exe(self.params, self.kv,
                              jnp.asarray(trows, jnp.int32),
                              jnp.asarray(toks, jnp.int32),
                              jnp.asarray(lens, jnp.int32),
                              jnp.asarray(sids, jnp.int32))
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_rows"] += int(lens.sum())
        first = np.asarray(jnp.argmax(logits[:, -1], -1), np.int64)
        now = time.perf_counter()
        for r, (slot, req) in enumerate(placed):
            self._emit(req, logits, r, -1)
            tok = int(first[r])
            req.out.append(tok)
            req.t_first = now
            self.tokens = self.tokens.at[slot, 0].set(tok)
            self.pos = self.pos.at[slot, 0].set(len(req.prompt))
            self.active[slot] = req
            self._admit_order += 1
            self._admit_seq[slot] = self._admit_order
            self.stats["admitted"] += 1
            if (self._prefix_ok
                    and len(req.prompt) >= self.prefix_min_tokens):
                self.stats["prefix_misses"] += 1   # served cold
                if _obs.REGISTRY.enabled:
                    self._m_pmiss.inc()
            self._register(slot, req)
            if len(req.out) >= req.budget:
                self._finish(slot)
        self._tables_dirty = True

    # -- decode-time page growth ------------------------------------------
    def _pre_step(self) -> None:
        """Before each step, make sure every active slot owns the page its
        next KV write lands in. A slot that can't get one (no free page,
        no preemptible victim) STALLS: its in-step KV write targets a
        missing page and is dropped by the scatter, its output token is
        discarded, and its tokens/pos don't advance — the identical step
        is retried once another request frees pages. When EVERY active
        slot is stalled (nothing will ever free) one victim is preempted
        to unblock the rest; a lone request larger than the entire pool
        is finished early with ``finish_reason="pool_exhausted"``."""
        self._stalled.clear()
        if _obs.REGISTRY.enabled:
            self._m_occupancy.set(self.pool.occupancy)
        ps = self.pool.page_size
        for i, req in enumerate(self.active):
            if req is None:
                continue
            nxt_pos = len(req.prompt) + len(req.out) - 1   # row written now
            v = nxt_pos % self.pool.vcap
            pg_idx = v // ps
            if pg_idx < self.pool.n_allocated(i):
                page = int(self.pool.table[i, pg_idx])
                # page in hand — but a shared page (pinned prefix, or the
                # ring wrapping back onto one) is immutable: copy-on-write
                # before this step's KV row lands in it
                if (self.pool.writable(i, page)
                        or self._cow(i, pg_idx, req.priority)):
                    continue
            elif self.pool.alloc(i, 1) is not None:
                self._tables_dirty = True
                continue
            elif self._ensure_pages(1, req.priority, exclude=i):
                self.pool.alloc(i, 1)
                self._tables_dirty = True
                continue
            self._stalled.add(i)
            self.stats["stall_steps"] += 1
        act = [i for i, r in enumerate(self.active) if r is not None]
        if act and all(i in self._stalled for i in act):
            # full-pool deadlock: nobody can free pages for anybody.
            # Preempt one victim (batch-class first, youngest-admitted
            # first — even an interactive victim restarts rather than
            # truncates) so the survivors decode on; each deadlock round
            # shrinks the resident set until it fits. Only a request
            # ALONE on the pool — the pool itself is smaller than its
            # demand — is finished early.
            victim = max(act, key=lambda i: (
                self.active[i].priority in self.scheduler.preemptible,
                self._admit_seq[i]))
            self._stalled.discard(victim)
            if len(act) == 1:
                self.stats["pool_exhausted"] += 1
                self._finish(victim, reason="pool_exhausted")
            else:
                self._preempt(victim)

    def _step_executable(self):
        if self._tables_dirty:
            self._tables_host = self.pool.table_array()
            self.tables = jnp.asarray(self._tables_host)
            self._tables_dirty = False
        keep = np.ones((self.slots,), bool)
        tbl, host = self.tables, self._tables_host
        if self._stalled:
            keep[list(self._stalled)] = False
            # a stalled slot must not write: a page-less stall drops its
            # KV write anyway, but a COW-stall's write would land in a
            # SHARED page — blank the whole row (its output is discarded
            # and the identical step is retried with the real table)
            host = self.pool.table_array()
            host[list(self._stalled)] = -1
            tbl = jnp.asarray(host)
        self._live = jnp.asarray(keep)
        logits, self.kv = self._step(self.params, self.kv, tbl,
                                     self.tokens, self.pos, self._live)
        if self.kernel == "pallas":
            # the decode kernel copies live table entries, skips the rest
            read = int(np.count_nonzero(host >= 0))
            skipped = host.size - read
            self.stats["kv_bytes_avoided"] += self._dense_view_bytes
            self.stats["kernel_pages_read"] += read
            self.stats["kernel_pages_skipped"] += skipped
            if _obs.REGISTRY.enabled:
                self._m_bytes.inc(self._dense_view_bytes)
                self._m_read.inc(read)
                self._m_skip.inc(skipped)
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        if self._stalled:
            # stalled slots hold position: same token, same pos, identical
            # retry next step (their page-less KV write was dropped and
            # `live` dropped their SSM-state write)
            self.tokens = jnp.where(keep[:, None], nxt[:, None], self.tokens)
            self.pos = self.pos + keep[:, None].astype(jnp.int32)
        else:
            self.tokens = nxt[:, None]
            self.pos = self.pos + 1
        return np.asarray(nxt), logits

    def pool_stats(self) -> Dict[str, float]:
        s = dict(self.pool.stats)
        s["occupancy"] = self.pool.occupancy
        s["free_pages"] = self.pool.free_pages
        s["pinned_prefixes"] = len(self.pool.prefix_keys())
        return s

    def kv_row_bytes(self) -> int:
        """Bytes one KV cache row costs across all attention layers (for
        bytes-on-wire style accounting of ``stats['prefill_rows']``)."""
        return self._kv_row_bytes
