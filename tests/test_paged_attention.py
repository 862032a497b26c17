"""In-kernel paged attention + copy-on-write prefix sharing.

Three layers of evidence, cheapest first:

* kernel vs oracle — ``kernels.paged_attention`` (interpret=True) against
  the dense ``kernels.ref.paged_attention_ref`` across page sizes,
  GQA/MQA, windows, softcap, the MLA two-component form, fragmented and
  permuted page tables, ragged/padded query batches, full-pool
  occupancy, and the decode form's ragged, holed and dead page blocks
  (allclose: same math, different reduction order);
* lm-level bit equality — ``paged_prefill``/``paged_decode_step`` with
  ``kernel="pallas"`` produce the SAME greedy tokens as the
  ``kernel="gather"`` dense-materialize baseline on bounded decode
  horizons (the two paths differ by 1 bf16 ulp in logits, so horizons
  are kept where argmax is stable — see EXPERIMENTS.md fig_serve_kernel);
* COW/refcount — ``PagePool`` share/cow/release invariants, and the
  serving-level guarantees: a pinned prefix is never corrupted by a
  sharer's divergent writes, preempting a sharing slot leaks nothing,
  and ``PagePool.check()`` stays clean through preemption-heavy runs.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models.lm import (lm_init, paged_cache_init, paged_decode_step,
                             paged_prefill)
from repro.serve.engine import PagedServeEngine, Request
from repro.serve.kv_pool import PagePool


def _mk(seed, B=2, S=1, H=4, K=2, Dk=16, Dv=16, P=12, ps=4, npps=4,
        filled=None, permute=True, q2dim=None):
    """Random paged-attention problem with a fragmented, permuted pool."""
    r = np.random.default_rng(seed)
    k = r.standard_normal((P, K, ps, Dk), np.float32)
    v = r.standard_normal((P, K, ps, Dv), np.float32)
    order = r.permutation(P) if permute else np.arange(P)
    tables = np.full((B, npps), -1, np.int32)
    kpos = np.full((P, ps), -1, np.int32)
    filled = [npps] * B if filled is None else filled
    n = 0
    for b in range(B):
        for j in range(filled[b]):
            pg = order[n]; n += 1
            tables[b, j] = pg
            kpos[pg] = j * ps + np.arange(ps)
    hist = np.asarray([f * ps for f in filled])
    q_pos = hist[:, None] - 1 + np.arange(S)[None]      # last S positions
    q = r.standard_normal((B, S, H, Dk), np.float32)
    q2 = k2 = None
    if q2dim:
        q2 = r.standard_normal((B, S, H, q2dim), np.float32)
        k2 = r.standard_normal((P, K, ps, q2dim), np.float32)
    to = jnp.asarray
    return (to(q), to(k), to(v), to(kpos, jnp.int32), to(tables, jnp.int32),
            to(q_pos, jnp.int32), (to(q2) if q2 is not None else None),
            (to(k2) if k2 is not None else None))


def _both(args, **kw):
    q, k, v, kpos, tables, q_pos, q2, k2 = args
    out = paged_attention(q, k, v, kpos, tables, q_pos, q2=q2, k2=k2,
                          interpret=True, block_q=8, **kw)
    ref = paged_attention_ref(q, k, v, kpos, tables, q_pos, q2=q2, k2=k2,
                              **kw)
    return out, ref


# ---------------------------------------------------------------------------
# Kernel vs dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps,npps", [(2, 6), (4, 4), (8, 2)])
def test_kernel_matches_ref_across_page_sizes(ps, npps):
    out, ref = _both(_mk(0, P=16, ps=ps, npps=npps))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("H,K", [(4, 4), (4, 2), (4, 1)])
def test_kernel_matches_ref_mha_gqa_mqa(H, K):
    out, ref = _both(_mk(1, H=H, K=K))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_ref_window_and_softcap():
    out, ref = _both(_mk(2), window=6, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_ref_mla_two_component():
    # absorbed MLA: scores = q_abs . ckv + q_rope . k_rope, shared V = ckv
    out, ref = _both(_mk(3, K=1, H=4, q2dim=8),
                     scale=1.0 / math.sqrt(16 + 8))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_ref_prefill_ragged_and_padded():
    # S=8 prefill rows with per-row histories; queries past the pad line
    # carry q_pos=-1 and must come back all-zero
    args = list(_mk(4, B=3, S=8, filled=[4, 2, 3]))
    q_pos = np.array(args[5])
    q_pos[1, 5:] = -1                                   # row 1: 5 real rows
    args[5] = jnp.asarray(q_pos)
    out, ref = _both(tuple(args))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(out)[1, 5:] == 0.0)


def test_kernel_matches_ref_partial_tables_full_pool():
    # every pool page allocated (full occupancy), slots with ragged page
    # counts including an EMPTY slot (all-dead table)
    out, ref = _both(_mk(5, B=4, P=12, npps=4, filled=[4, 0, 3, 4]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(out)[1] == 0.0)            # dead slot -> zeros


# Decode form (S == 1): the grid walks (slot, page block); one block covers
# every kv head and DECODE_BLOCK_BYTES // (K*ps*Dk*itemsize) pages. The
# shapes below (float32, 128-lane minor dims) make that 4 pages (16 for
# the MLA case), so blocks end ragged and holes fall inside a block.
def _decode_case(case):
    kw = dict(B=2, H=8, K=4, Dk=512, Dv=512, ps=8, npps=10, P=24)
    if case == "npps_not_multiple":
        args, extra = _mk(10, **kw), {}
    elif case == "hole_before_live":
        args, extra = list(_mk(11, filled=[7, 9], **kw)), {}
        tables = np.array(args[4])
        tables[0, 1] = -1                       # slot 0: a hole in block 0
        tables[1, [4, 5, 7]] = -1               # slot 1: block 1 keeps page 6
        args[4] = jnp.asarray(tables)
    elif case == "every_slot_dead":
        args, extra = _mk(12, filled=[0, 0], **kw), {}
    elif case == "mla_k1_two_component":
        kw.update(H=4, K=1, npps=20, P=40)
        args = _mk(13, q2dim=64, **kw)
        extra = dict(scale=1.0 / math.sqrt(512 + 64))
    elif case == "window_softcap":
        args, extra = _mk(14, filled=[10, 6], **kw), dict(window=37,
                                                          softcap=30.0)
    elif case == "live_ends_mid_block":
        args, extra = _mk(15, filled=[6, 3], **kw), {}
    return args, extra, kw


@pytest.mark.parametrize("case", [
    "npps_not_multiple", "hole_before_live", "every_slot_dead",
    "mla_k1_two_component", "window_softcap", "live_ends_mid_block"])
def test_decode_form_matches_ref(case):
    from repro.kernels.paged_attention import DECODE_BLOCK_BYTES
    args, extra, kw = _decode_case(case)
    ppb = DECODE_BLOCK_BYTES // (kw["K"] * kw["ps"] * kw["Dk"] * 4)
    assert 1 < ppb < kw["npps"] and kw["npps"] % ppb     # blocks end ragged
    out, ref = _both(args, **extra)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    dead = np.all(np.asarray(args[4]) < 0, axis=1)
    assert np.all(np.asarray(out)[dead] == 0.0)          # dead slot -> zeros


# ---------------------------------------------------------------------------
# lm-level: pallas vs gather greedy-token equality
# ---------------------------------------------------------------------------

def _greedy(cfg, params, kernel, seed, steps=4, slots=3, ps=4, npps=8,
            P=None):
    """Prefill + greedy decode over a fragmented paged pool; returns the
    (steps+1, slots) token matrix plus the matching top-2 logit gap at
    every emitted token (argmax stability margin, in f32)."""
    P = P if P is not None else slots * npps
    r = np.random.default_rng(seed)
    perm = r.permutation(P)
    tables = np.full((slots, npps), -1, np.int32)
    n = 0
    for b in range(slots):
        tables[b, :npps - 1] = perm[n:n + npps - 1]
        n += npps - 1
    tables = jnp.asarray(tables)
    S = 8
    toks = jnp.asarray(r.integers(1, cfg.vocab, (slots, S)), jnp.int32)
    lens = jnp.asarray(r.integers(2, S + 1, (slots,)), jnp.int32)
    sids = jnp.arange(slots, dtype=jnp.int32)
    pool = paged_cache_init(cfg, slots, P, ps)
    lg, pool = paged_prefill(params, pool, tables, toks, lens, sids, cfg,
                             kernel=kernel)
    def _gap(row_logits):                               # (slots, vocab)
        top2 = jax.lax.top_k(row_logits.astype(jnp.float32), 2)[0]
        return np.asarray(top2[:, 0] - top2[:, 1])

    seq = [np.asarray(jnp.argmax(lg[:, 0], -1))]
    gaps = [_gap(lg[:, 0])]
    pos = lens[:, None].astype(jnp.int32)
    t = jnp.argmax(lg[:, 0], -1)[:, None].astype(jnp.int32)
    for _ in range(steps):
        lg, pool = paged_decode_step(params, pool, tables, t, pos, cfg,
                                     kernel=kernel)
        t = jnp.argmax(lg, -1).astype(jnp.int32)
        seq.append(np.asarray(t[:, 0]))
        gaps.append(_gap(lg[:, 0]))
        pos = pos + 1
    return np.stack(seq), np.stack(gaps)


# the two kernels reduce the softmax in different orders, so logits agree
# only to ~1 bf16 ulp (~8e-3 at unit scale); a greedy argmax sitting on a
# near-tie may legitimately flip.  Equality contract: token streams match
# exactly until a slot hits a near-tie (top-2 gap below a few ulps); past
# that flip the slot's histories differ and tokens are unconstrained.
_ULP_TIE = 0.05


def _assert_tokens_match_modulo_ties(a, ga, b, ctx):
    assert a.shape == b.shape, ctx
    for s in range(a.shape[1]):                         # slots independent
        col = np.nonzero(a[:, s] != b[:, s])[0]
        if col.size:
            first = col[0]
            assert ga[first, s] < _ULP_TIE, (
                ctx, s, first, float(ga[first, s]),
                a[:, s].tolist(), b[:, s].tolist())


@pytest.mark.parametrize("arch", ["qwen3-14b", "deepseek-v2-236b"])
def test_lm_tokens_pallas_equals_gather(arch):
    cfg = get_config(arch, smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    for seed in (1, 2):
        a, ga = _greedy(cfg, params, "gather", seed)
        b, _ = _greedy(cfg, params, "pallas", seed)
        _assert_tokens_match_modulo_ties(a, ga, b, (arch, seed))


def test_lm_tokens_equal_at_full_occupancy():
    # every pool page owned by some slot: the kernel sees zero dead pages
    cfg = get_config("qwen3-14b", smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    kw = dict(slots=2, ps=2, npps=6, P=10)              # 2*(6-1) pages used
    a, ga = _greedy(cfg, params, "gather", 7, **kw)
    b, _ = _greedy(cfg, params, "pallas", 7, **kw)
    _assert_tokens_match_modulo_ties(a, ga, b, "full-occupancy")


# ---------------------------------------------------------------------------
# PagePool refcounts + COW (host-side invariants)
# ---------------------------------------------------------------------------

def test_pool_refcount_share_cow_release():
    pool = PagePool(n_pages=16, page_size=4, slots=4, pages_per_slot=6)
    assert pool.alloc(0, 3) is not None
    pages = pool.pages_of(0)
    assert pool.register_prefix(b"k", list(range(8)), pages[:2])
    pool.check()
    # registered pages are pinned and no longer writable by their owner
    assert not pool.writable(0, pages[0]) and pool.writable(0, pages[2])
    e = pool.lookup_prefix(b"k", list(range(9)))
    assert e is not None and e["pages"] == pages[:2]
    assert pool.lookup_prefix(b"k", [0, 1, 99]) is None  # token-verified
    assert pool.share(1, e["pages"]) and pool.alloc(1, 1) is not None
    pool.check()
    assert int(pool.refcount[pages[0]]) == 3             # slot0+slot1+registry
    # COW: slot 1 breaks the boundary page out; the original stays shared
    src, dst = pool.cow_page(1, 1)
    assert src == pages[1] and pool.writable(1, dst)
    pool.check()
    # releases free only refcount-zero pages
    assert pool.free_slot(0) == [pages[2]]
    freed = pool.free_slot(1)
    assert pages[0] not in freed and dst in freed
    pool.check()
    assert int(pool.refcount[pages[0]]) == 1             # registry pin only
    assert set(pool.drop_prefix(b"k")) == set(pages[:2])
    pool.check()
    assert pool.free_pages == pool.n_pages


def test_pool_prefix_eviction_lru():
    pool = PagePool(n_pages=8, page_size=4, slots=4, pages_per_slot=4)
    for s, key in enumerate([b"a", b"b"]):
        pool.alloc(s, 2)
        pool.register_prefix(key, [s] * 8, pool.pages_of(s))
        pool.free_slot(s)
    pool.check()
    assert pool.free_pages == 4
    pool.lookup_prefix(b"a", [0] * 8)                    # touch a: b is LRU
    pool.evict_prefixes(6)
    assert pool.prefix_keys() == [b"a"]
    pool.check()
    pool.evict_prefixes(pool.n_pages)
    assert pool.free_pages == pool.n_pages
    pool.check()


def test_pool_check_catches_refcount_leak():
    pool = PagePool(n_pages=8, page_size=4, slots=2, pages_per_slot=4)
    pool.alloc(0, 2)
    pool.refcount[pool.pages_of(0)[0]] += 1              # corrupt on purpose
    with pytest.raises(AssertionError):
        pool.check()


# ---------------------------------------------------------------------------
# Serving-level COW: the pinned prefix survives its sharers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen3-14b", smoke=True)
    return cfg, lm_init(jax.random.PRNGKey(0), cfg)


def test_warm_divergence_does_not_corrupt_prefix(qwen):
    """Mid-page divergence: the sharer COWs the boundary page and writes
    its suffix into the copy; replaying the ORIGINAL prompt afterwards
    still yields the original continuation."""
    cfg, params = qwen
    r = np.random.default_rng(3)
    p1 = r.integers(1, cfg.vocab, 18)                    # boundary mid-page
    eng = PagedServeEngine(cfg, params, slots=4, page_size=4,
                           pages_per_slot=8, pool_pages=28, kernel="gather",
                           prefix_sharing=True)
    a = Request(rid=0, prompt=p1.copy(), max_new=4)
    eng.run([a])
    eng.pool.check()
    assert eng.stats["prefix_registered"] == 1
    # sharer diverges inside the boundary page
    p2 = np.concatenate([p1, r.integers(1, cfg.vocab, 5)])
    b = Request(rid=1, prompt=p2, max_new=4)
    eng.run([b])
    eng.pool.check()
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["cow_pages"] >= 1
    # replay the original prompt: warm again, same tokens as the cold run
    c = Request(rid=2, prompt=p1.copy(), max_new=4)
    eng.run([c])
    eng.pool.check()
    assert eng.stats["prefix_hits"] == 2
    assert c.out == a.out, (a.out, c.out)


def test_warm_prefill_writes_only_suffix(qwen):
    cfg, params = qwen
    r = np.random.default_rng(4)
    p1 = r.integers(1, cfg.vocab, 16)                    # page-aligned
    eng = PagedServeEngine(cfg, params, slots=4, page_size=4,
                           pages_per_slot=8, pool_pages=28,
                           prefix_sharing=True)
    eng.run([Request(rid=0, prompt=p1.copy(), max_new=2)])
    cold_rows = eng.stats["prefill_rows"]
    assert cold_rows == 16
    p2 = np.concatenate([p1, r.integers(1, cfg.vocab, 6)])
    eng.run([Request(rid=1, prompt=p2, max_new=2)])
    assert eng.stats["prefill_rows"] - cold_rows == 6    # suffix only
    assert eng.stats["prefix_hits"] == 1
    eng.pool.check()


def test_preempted_sharer_leaks_nothing(qwen):
    """A batch-class sharer preempted mid-decode releases its references;
    the pinned prefix stays intact for its next (re)admission and
    ``check()`` stays clean throughout."""
    cfg, params = qwen
    r = np.random.default_rng(5)
    p1 = r.integers(1, cfg.vocab, 16)
    eng = PagedServeEngine(cfg, params, slots=2, page_size=4,
                           pages_per_slot=8, pool_pages=12,
                           prefix_sharing=True)
    eng.run([Request(rid=0, prompt=p1.copy(), max_new=2)])
    eng.pool.check()
    # sharer (batch class) + an interactive flood that preempts it
    sharer = Request(rid=1, prompt=np.concatenate(
        [p1, r.integers(1, cfg.vocab, 4)]), max_new=24, priority="batch")
    flood = [Request(rid=2 + i, prompt=r.integers(1, cfg.vocab, 8),
                     max_new=16) for i in range(3)]
    eng.run([sharer] + flood)
    eng.pool.check()                                     # zero leaks
    assert sharer.done
    # all references released: only registry pins remain
    held = int(np.sum(eng.pool.refcount > 0))
    pinned = sum(len(eng.pool._prefix[k]["pages"])
                 for k in eng.pool.prefix_keys())
    assert held == pinned


def test_preemption_heavy_mixed_run_stays_clean(qwen):
    """Oversubscribed pool + shared prefixes + preemption churn: every
    request completes and the allocator invariants hold at the end."""
    cfg, params = qwen
    r = np.random.default_rng(6)
    base = r.integers(1, cfg.vocab, 12)
    reqs = []
    for i in range(8):
        if i % 2 == 0:
            prompt = np.concatenate([base, r.integers(1, cfg.vocab, 1 + i)])
        else:
            prompt = r.integers(1, cfg.vocab, 8 + i)
        reqs.append(Request(rid=i, prompt=prompt, max_new=6,
                            priority="batch" if i % 3 == 0 else "interactive"))
    eng = PagedServeEngine(cfg, params, slots=3, page_size=4,
                           pages_per_slot=8, pool_pages=14,
                           prefix_sharing=True)
    stats = eng.run(reqs)
    eng.pool.check()
    assert stats["decoded"] > 0
    done = [q for q in reqs if q.done]
    assert len(done) == len(reqs)


# Pallas vs gather logit tolerance: the kernel's online softmax sums in
# another order than the gather path's dense softmax, so an attention
# output may round to a neighbouring bf16 value in any layer; 4 bf16 ulps
# at |logit| in [1, 2) bounds what that moves a logit of the smoke model.
_LOGIT_TOL = 4 * 2.0 ** -7


def test_engine_tokens_identical_dense_gather_pallas(qwen):
    """One short trace through all three serving paths — the fixed-ring
    dense engine, the paged gather engine, and the paged in-kernel engine
    (interpret mode off-TPU). Every logits row a request gets (prefill,
    then each decode step) matches the gather engine's: bit for bit for
    the dense engine, which runs the same dense view, and within
    ``_LOGIT_TOL`` for the kernel. Greedy tokens may part only where the
    gather logits hold a top-2 tie within that tolerance; past that
    point the two histories differ and are not compared."""
    from repro.serve.engine import ServeEngine
    cfg, params = qwen

    def trace():
        r = np.random.default_rng(9)
        return [Request(rid=i, prompt=r.integers(1, cfg.vocab, 6 + 2 * i),
                        max_new=3) for i in range(3)]

    runs = {}
    for name, mk in (
            ("dense", lambda: ServeEngine(cfg, params, slots=2,
                                          capacity=16)),
            ("gather", lambda: PagedServeEngine(cfg, params, slots=2,
                                                page_size=4,
                                                pages_per_slot=4,
                                                kernel="gather")),
            ("pallas", lambda: PagedServeEngine(cfg, params, slots=2,
                                                page_size=4,
                                                pages_per_slot=4,
                                                kernel="pallas"))):
        t = trace()
        logits = {r.rid: [] for r in t}
        eng = mk()
        eng.logit_sink = lambda req, row, lg=logits: lg[req.rid].append(
            np.asarray(row, np.float32))
        eng.run(t, max_steps=500)
        assert all(r.done for r in t)
        assert all(len(logits[r.rid]) == len(r.out) == 3 for r in t)
        runs[name] = (t, logits)

    ref_t, ref_lg = runs["gather"]
    for name, tol in (("dense", 0.0), ("pallas", _LOGIT_TOL)):
        t, lg = runs[name]
        for r, r0 in zip(t, ref_t):
            for step, (a, b) in enumerate(zip(lg[r.rid], ref_lg[r0.rid])):
                np.testing.assert_allclose(
                    a, b, rtol=0, atol=tol, err_msg=f"{name} rid={r.rid} "
                    f"step={step}")
                if r.out[step] != r0.out[step]:
                    top2 = np.sort(b)[-2:]
                    assert top2[1] - top2[0] <= tol, (name, r.rid, step)
                    break


def test_engine_counts_kernel_pages_read_and_skipped(qwen):
    """``serve.kernel.pages_read`` is the live table entries handed to the
    decode kernel, summed over steps; read + skipped covers every entry of
    every step's table."""
    from repro.obs import REGISTRY, disable_observability, enable_observability
    cfg, params = qwen
    r = np.random.default_rng(10)
    reqs = [Request(rid=i, prompt=r.integers(1, cfg.vocab, 5 + 4 * i),
                    max_new=4 + 3 * i) for i in range(3)]
    eng = PagedServeEngine(cfg, params, slots=2, page_size=4,
                           pages_per_slot=6, kernel="pallas")
    live = []
    step = eng._step

    def counting_step(p, kv, tables, *rest):
        live.append(int(np.count_nonzero(np.asarray(tables) >= 0)))
        return step(p, kv, tables, *rest)

    eng._step = counting_step
    REGISTRY.clear()
    enable_observability()
    try:
        stats = eng.run(reqs, max_steps=500)
        read = REGISTRY.counter("serve.kernel.pages_read").value
        skipped = REGISTRY.counter("serve.kernel.pages_skipped").value
    finally:
        disable_observability()
        REGISTRY.clear()
    assert all(q.done for q in reqs)
    assert len(live) == stats["steps"] > 0
    assert read == stats["kernel_pages_read"] == sum(live) > 0
    assert skipped == stats["kernel_pages_skipped"] > 0
    assert read + skipped == stats["steps"] * eng.slots * eng.pool.pages_per_slot
