"""Serving subsystem: slot admission, continuous decode, the paged KV
pool (alloc/free invariants, batched prefill, priority preemption), and
capacity guards."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.lm import lm_init
from repro.serve.engine import PagedServeEngine, Request, ServeEngine
from repro.serve.kv_pool import PagePool
from repro.serve.scheduler import AdmissionScheduler, bucket_len


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-14b", smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(cfg, n, lens=(8, 12, 16), max_new=6, batch_every=0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(lens[i % len(lens)])),
                    max_new=max_new,
                    priority=("batch" if batch_every
                              and i % batch_every == 0 else "interactive"))
            for i in range(n)]


# ---------------------------------------------------------------------------
# Fixed-partition baseline (seed behavior must survive the rework)
# ---------------------------------------------------------------------------

def test_engine_completes_requests(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, slots=4, capacity=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=6) for i in range(6)]
    stats = eng.run(reqs, max_steps=200)
    assert all(r.done for r in reqs)
    assert all(len(r.out) >= r.max_new for r in reqs)
    assert stats["admitted"] == 6
    assert stats["decoded"] > 0


def test_engine_batches_share_steps(setup):
    """Continuous batching: 4 concurrent requests must cost far fewer steps
    than 4 sequential ones (the array-launch property at the serving layer)."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    eng = ServeEngine(cfg, params, slots=4, capacity=64)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=10) for i in range(4)]
    stats = eng.run(reqs, max_steps=200)
    assert stats["steps"] <= 15, stats   # ~10 shared steps, not 40


# ---------------------------------------------------------------------------
# PagePool allocator invariants
# ---------------------------------------------------------------------------

def test_pool_alloc_free_invariants():
    pool = PagePool(n_pages=16, page_size=8, slots=4, pages_per_slot=8)
    assert pool.alloc(0, 3) is not None
    assert pool.alloc(1, 5) is not None
    pool.check()
    assert pool.used_pages == 8 and pool.free_pages == 8
    assert pool.stats["watermark"] == 8
    assert pool.n_allocated(0) == 3 and pool.pages_of(1)[0] >= 0
    freed = pool.free_slot(0)
    assert len(freed) == 3
    pool.check()
    assert pool.used_pages == 5
    # watermark is a high-water mark, not current occupancy
    assert pool.stats["watermark"] == 8


def test_pool_exhaustion_and_fragmented_reuse():
    pool = PagePool(n_pages=8, page_size=4, slots=4, pages_per_slot=4)
    assert pool.alloc(0, 4) is not None
    assert pool.alloc(1, 4) is not None
    assert pool.alloc(2, 1) is None                 # pool empty
    assert pool.stats["alloc_failures"] == 1
    pool.free_slot(0)                               # fragmented free list
    got = pool.alloc(2, 3)
    assert got is not None and len(got) == 3
    pool.check()
    # a slot can never exceed its table width, even with free pages around
    pool.free_slot(1)
    assert pool.alloc(2, 2) is None                 # 3 + 2 > pages_per_slot
    pool.check()
    pool.reset()
    assert pool.free_pages == 8 and pool.used_pages == 0
    pool.check()


# ---------------------------------------------------------------------------
# Scheduler policy
# ---------------------------------------------------------------------------

def test_scheduler_priority_order_and_bucket_groups():
    sched = AdmissionScheduler()
    mk = lambda rid, n, p: Request(rid=rid, prompt=np.zeros(n, np.int64),  # noqa: E731
                                   max_new=1, priority=p)
    for r in (mk(0, 8, "batch"), mk(1, 9, "interactive"),
              mk(2, 12, "interactive"), mk(3, 20, "batch")):
        sched.enqueue(r, now=0.0)
    # head is the first INTERACTIVE despite batch arriving first; its
    # bucket (16) pulls rid 2 (bucket 16) and rid 0 (bucket 8) / rid 3
    # (bucket 32) stay queued in place
    group = sched.pop_group(max_n=4)
    assert [r.rid for r in group] == [1, 2]
    assert [r.rid for r in [sched.pop_next(), sched.pop_next()]] == [0, 3]
    assert bucket_len(9) == 16 and bucket_len(8) == 8 and bucket_len(1) == 8


def test_scheduler_slo_gates_preemption():
    sched = AdmissionScheduler(target_first_result_s=10.0)
    assert not sched.should_preempt(now=100.0)      # nothing interactive
    req = Request(rid=0, prompt=np.zeros(4, np.int64), max_new=1)
    sched.enqueue(req, now=100.0)
    assert not sched.should_preempt(now=101.0)      # wait 1s < 0.5 * SLO
    assert sched.should_preempt(now=105.0)          # wait >= 0.5 * SLO
    # without an SLO, interactive work preempts immediately
    eager = AdmissionScheduler()
    eager.enqueue(Request(rid=1, prompt=np.zeros(4, np.int64), max_new=1),
                  now=0.0)
    assert eager.should_preempt(now=0.0)


# ---------------------------------------------------------------------------
# Paged engine: equivalence, batched prefill, preemption, oversubscription
# ---------------------------------------------------------------------------

def test_paged_tokens_bit_identical_to_fixed(setup):
    """Acceptance: the paged engine's token output matches the fixed-
    partition engine on the same trace — with more requests than slots, so
    pages are freed, cleared, and reused across admissions."""
    cfg, params = setup
    reqs_d = _trace(cfg, 8)
    reqs_p = _trace(cfg, 8)
    dense = ServeEngine(cfg, params, slots=4, capacity=64)
    dense.run(reqs_d, max_steps=400)
    paged = PagedServeEngine(cfg, params, slots=4, page_size=8,
                             pages_per_slot=8, batched_prefill=False)
    paged.run(reqs_p, max_steps=400)
    assert all(r.done for r in reqs_d) and all(r.done for r in reqs_p)
    for a, b in zip(reqs_d, reqs_p):
        assert a.out == b.out, (a.rid, a.out, b.out)
    paged.pool.check()
    assert paged.pool.used_pages == 0                # everything freed


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "zamba2-7b"])
def test_paged_identity_across_cache_layouts(arch):
    """MLA caches (ckv/kr leaves) and hybrid attn+SSM caches (slot-dense
    state beside paged pages; exact-length prefill groups — padding is
    unsound for the SSM recurrence) go through the same paged paths."""
    cfg = get_config(arch, smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    mk = lambda: _trace(cfg, 4, lens=(6, 9), max_new=4, seed=11)  # noqa: E731
    reqs_d, reqs_p = mk(), mk()
    ServeEngine(cfg, params, slots=2, capacity=32).run(reqs_d, max_steps=200)
    paged = PagedServeEngine(cfg, params, slots=2, page_size=4,
                             pages_per_slot=8, batched_prefill=False)
    paged.run(reqs_p, max_steps=200)
    assert all(r.done for r in reqs_d) and all(r.done for r in reqs_p)
    for a, b in zip(reqs_d, reqs_p):
        assert a.out == b.out, (arch, a.rid, a.out, b.out)
    paged.pool.check()


def test_stall_is_value_neutral_for_ssm_state():
    """A stalled (page-less) slot's retry must be IDENTICAL: its attention
    write drops on the missing page and the ``live`` mask drops its
    SSM-state write — without it the recurrence absorbs the stalled token
    twice and a hybrid model's tokens diverge from the dense engine."""
    cfg = get_config("zamba2-7b", smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    mk = lambda: _trace(cfg, 4, lens=(6, 9), max_new=6, seed=13)  # noqa: E731
    reqs_d, reqs_p = mk(), mk()
    ServeEngine(cfg, params, slots=2, capacity=16).run(reqs_d, max_steps=300)
    paged = PagedServeEngine(cfg, params, slots=2, page_size=2,
                             pages_per_slot=8, pool_pages=8,
                             batched_prefill=False)
    stats = paged.run(reqs_p, max_steps=600)
    assert stats["stall_steps"] > 0          # pressure actually happened
    assert stats["pool_exhausted"] == 0
    assert all(r.done for r in reqs_p)
    for a, b in zip(reqs_d, reqs_p):
        assert a.out == b.out, (a.rid, a.out, b.out)


def test_batched_prefill_matches_one_slot_tokens(setup):
    """Batched multi-slot prefill (one padded executable for the whole
    admission group) must produce the same tokens as the one-slot loop."""
    cfg, params = setup
    reqs_1 = _trace(cfg, 8, seed=3)
    reqs_b = _trace(cfg, 8, seed=3)
    one = PagedServeEngine(cfg, params, slots=4, page_size=8,
                           pages_per_slot=8, batched_prefill=False)
    one.run(reqs_1, max_steps=400)
    bat = PagedServeEngine(cfg, params, slots=4, page_size=8,
                           pages_per_slot=8, batched_prefill=True)
    bat.run(reqs_b, max_steps=400)
    for a, b in zip(reqs_1, reqs_b):
        assert a.out == b.out, (a.rid, a.out, b.out)
    # the batched engine packed admissions: strictly fewer dispatches
    assert bat.stats["prefill_dispatches"] < one.stats["prefill_dispatches"]
    assert one.stats["prefill_dispatches"] == len(reqs_1)


def test_interactive_preempts_batch(setup):
    """Priority preemption ordering: batch-class work occupying the full
    pool is evicted (youngest first, requeued, restarted) the moment an
    interactive request needs the slots/pages, and the interactive request
    finishes first."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    b1, b2 = (Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                      max_new=8, priority="batch") for i in (0, 1))
    eng = PagedServeEngine(cfg, params, slots=2, page_size=4,
                           pages_per_slot=4, pool_pages=4)
    eng.scheduler.enqueue(b1)
    eng.scheduler.enqueue(b2)
    assert eng._admit() == 2                        # pool now full (2x2)
    i1 = Request(rid=2, prompt=rng.integers(0, cfg.vocab, size=8), max_new=4,
                 priority="interactive")
    eng.scheduler.enqueue(i1)
    assert eng._admit() == 1                        # preempted b2 for i1
    assert b2.preemptions == 1 and b2.out == [] and b2.t_first is None
    assert any(r is i1 for r in eng.active)
    stats = eng.run([], max_steps=400)              # drain
    assert all(r.done for r in (b1, b2, i1))
    assert i1.t_done <= b2.t_done                   # interactive first
    assert stats["preemptions"] >= 1
    assert stats["classes"]["batch"]["preemptions"] >= 1
    eng.pool.check()


def test_oversubscribed_pool_completes(setup):
    """Requests >> slots over a pool well below the static partition
    (12 pages vs 4 slots x 4): everything still finishes at full budget
    (batch work preempted/requeued under pressure, pages recycled), and
    interactive p50 TTFT <= batch p50 TTFT."""
    cfg, params = setup
    reqs = _trace(cfg, 16, max_new=10, batch_every=2, seed=6)
    eng = PagedServeEngine(cfg, params, slots=4, page_size=8,
                           pages_per_slot=4, pool_pages=12)
    stats = eng.run(reqs, max_steps=3000)
    assert all(r.done for r in reqs)
    assert stats["pool_exhausted"] == 0             # never truncated
    assert all(len(r.out) == r.max_new for r in reqs)
    cls = stats["classes"]
    assert cls["interactive"]["p50_ttft_s"] <= cls["batch"]["p50_ttft_s"]
    eng.pool.check()
    assert eng.pool.used_pages == 0


def test_overflow_guard_rejects_and_clamps(setup):
    """Silent-KV-overflow fix: an unservable prompt is rejected at admit;
    a too-long generation is finished at capacity — both surfaced in
    stats, on both engines."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    for mk in (lambda: ServeEngine(cfg, params, slots=2, capacity=32),
               lambda: PagedServeEngine(cfg, params, slots=2, page_size=8,
                                        pages_per_slot=4)):
        eng = mk()
        too_long = Request(rid=0, prompt=rng.integers(0, cfg.vocab, size=40),
                           max_new=4)
        clamped = Request(rid=1, prompt=rng.integers(0, cfg.vocab, size=8),
                          max_new=100)
        stats = eng.run([too_long, clamped], max_steps=400)
        assert too_long.done and not too_long.out
        assert too_long.finish_reason == "rejected_over_capacity"
        assert stats["rejected_over_capacity"] == 1
        # prompt rows [0,8) + fed-back tokens: 8 + budget - 1 <= 32
        assert clamped.done and len(clamped.out) == 32 - 8 + 1
        assert clamped.finish_reason == "capacity"
        assert stats["capacity_clamped"] == 1


def test_request_records_and_class_summary(setup):
    cfg, params = setup
    reqs = _trace(cfg, 6, max_new=4, batch_every=3, seed=8)
    eng = PagedServeEngine(cfg, params, slots=4, page_size=8,
                           pages_per_slot=8)
    stats = eng.run(reqs, max_steps=400)
    assert len(eng.records) == 6
    for rec in eng.records:
        assert rec.ttft_s > 0 and rec.n_tokens == 4
    assert set(stats["classes"]) == {"interactive", "batch"}
    assert stats["classes"]["interactive"]["n"] == 4


def _stamps_in_order(req):
    assert req.t_enqueue <= req.t_admit <= req.t_first <= req.t_done
    rec = req.record()
    assert rec.queue_s == pytest.approx(req.t_admit - req.t_enqueue)
    assert 0.0 <= rec.queue_s <= rec.ttft_s


@pytest.mark.parametrize("path", ["cold", "warm", "preempted"])
def test_admission_stamp_orders_the_request_stamps(setup, path):
    """``t_admit`` is stamped when a request takes a slot, before its
    prefill is dispatched, on a cold and a warm (prefix-hit) admission;
    preemption clears it with ``t_first`` and re-admission stamps it
    anew. ``RequestRecord.queue_s`` is the stamp less ``t_enqueue``."""
    from repro.core.telemetry import serve_table
    cfg, params = setup
    rng = np.random.default_rng(11)
    if path == "preempted":
        b1, b2 = (Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                          max_new=8, priority="batch") for i in (0, 1))
        eng = PagedServeEngine(cfg, params, slots=2, page_size=4,
                               pages_per_slot=4, pool_pages=4)
        eng.scheduler.enqueue(b1)
        eng.scheduler.enqueue(b2)
        assert eng._admit() == 2
        first_admit = b2.t_admit
        assert first_admit is not None
        eng.scheduler.enqueue(Request(rid=2, prompt=rng.integers(
            0, cfg.vocab, size=8), max_new=4))
        assert eng._admit() == 1                    # b2 preempted
        assert b2.preemptions == 1
        assert b2.t_admit is None and b2.t_first is None
        eng.run([], max_steps=400)
        assert b2.t_admit > first_admit
        reqs = [b1, b2]
    else:
        eng = PagedServeEngine(cfg, params, slots=2, page_size=4,
                               pages_per_slot=8, pool_pages=16,
                               prefix_sharing=True)
        p1 = rng.integers(1, cfg.vocab, 16)
        reqs = [Request(rid=0, prompt=p1, max_new=4)]
        if path == "warm":
            eng.run(reqs)
            reqs = [Request(rid=1, prompt=np.concatenate(
                [p1, rng.integers(1, cfg.vocab, 4)]), max_new=4)]
        eng.run(reqs)
        assert eng.stats["prefix_hits"] == (path == "warm")
    for r in reqs:
        assert r.done
        _stamps_in_order(r)
    table = serve_table(eng.records).splitlines()
    assert table[0].split(",")[2] == "queue_s"
    assert float(table[-1].split(",")[2]) == pytest.approx(
        eng.records[-1].queue_s, abs=1e-4)
