"""Paged attention Pallas TPU kernel: in-kernel page-table walk.

The serving engine keeps KV state in one shared pool of fixed-size pages;
each slot owns an ordered page list (its page table, -1 = unallocated).
The XLA path materializes a dense per-slot view every step
(``models.lm.paged_gather`` -> attention -> ``paged_scatter``), touching
``slots x pages_per_slot x page_size`` rows whether or not they are
allocated. This kernel never materializes that view: the page table rides
in as a *scalar-prefetch* operand, so each key-block's BlockSpec index map
reads ``tables[slot, j]`` and DMAs exactly that pool page into VMEM —
block-indexed loads straight from the pool, online-softmax accumulation
per page block, with dead pages (table entry -1), empty rows (pos -1),
causality and sliding windows all neutralized in-kernel.

Layout (what Mosaic's tiling rule — the last two block dims divisible by
(8, 128) or equal to the array's — forces): the pool is head-major,
``(P, K, ps, D)``, so one grid step's K/V block ``(1, 1, ps, D)`` is one
page of one kv head with ``(ps, D)`` as its full trailing dims at any page
size. Queries are regrouped per kv head into ``(B, K, S*G, D)`` rows
(row ``r`` = query ``r // G``, head ``r % G`` of the group), so both
matmuls are plain 2-D ``(rows, D) x (ps, D)^T`` and ``(rows, ps) x
(ps, D)``; positions ride as a ``(1, ps)`` key row and a ``(rows, 1)``
query column.

Prefill (S > 1, up to the virtual capacity) walks the grid (slots,
kv_heads, q_blocks, pages_per_slot) with the page axis innermost, so
softmax statistics live in VMEM scratch across the walk (TPU grids
execute the trailing axis sequentially).

Decode (S == 1) walks a coarser grid, (slots, page blocks): one step
covers every kv head and ``ppb`` pages, about ``DECODE_BLOCK_BYTES`` of
K (8 pages at qwen3-14b widths). The pool stays in HBM and the kernel
copies only live pages itself — one DMA per page of every kv head into a
double-buffered VMEM block, the next block's copies in flight while this
one computes — and a block with no live page costs one empty step. The
page-block axis is innermost, so the statistics again stay in scratch.

An optional second score component (``q2``/``k2``) supports MLA's
weight-absorbed decode form — scores are ``q.k + q2.k2`` (= q_abs.ckv +
q_rope.kr) against the compressed cache — without ever concatenating
pool-resident leaves.

Numerics follow the XLA gather path (``models.attention._attn_flat``): the
softmax scale is folded into the queries before the matmul, scores and
statistics accumulate in float32, and probabilities meet V in V's dtype.
CPU runs use ``interpret=True`` (validated against
``ref.paged_attention_ref``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (positions x heads per kv head) in one grid step: bounds the
# VMEM the q block, the f32 accumulator and the score tile take when a kv
# head serves many query heads (MLA: 128)
MAX_ROWS = 1024
# bytes of K one decode grid step covers (a page of every kv head is
# K * ps * Dk elements): few, large steps over live pages only
DECODE_BLOCK_BYTES = 256 * 1024


def _kernel(tbl_ref, *refs, causal: bool, window, cap, has_q2: bool):
    if has_q2:
        q_ref, k_ref, v_ref, kpos_ref, qpos_ref, q2_ref, k2_ref = refs[:7]
        o_ref, m_sc, l_sc, acc_sc = refs[7:]
    else:
        q_ref, k_ref, v_ref, kpos_ref, qpos_ref = refs[:5]
        o_ref, m_sc, l_sc, acc_sc = refs[5:]
    b = pl.program_id(0)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    t = tbl_ref[b, j]

    @pl.when(t >= 0)
    def _block():
        nt = (((1,), (1,)), ((), ()))                        # a @ b.T
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], nt,  # (R, ps)
                                preferred_element_type=jnp.float32)
        if has_q2:
            s += jax.lax.dot_general(q2_ref[0, 0], k2_ref[0, 0], nt,
                                     preferred_element_type=jnp.float32)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        kp = kpos_ref[0]                                     # (1, ps)
        qp = qpos_ref[0]                                     # (R, 1)
        mask = jnp.broadcast_to(kp >= 0, s.shape)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]                                   # (R, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        m_sc[...] = m_new
        v = v_ref[0, 0]                                      # (ps, Dv)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_sc[...]
        l = jnp.where(l > 0, l, 1.0)                         # dead slot -> 0
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _decode_kernel(tbl_ref, qpos_ref, *refs, ppb: int, ps: int, causal: bool,
                   window, cap, has_q2: bool):
    """One grid step: slot b, pages ``j*ppb ..`` of its table, every kv head.

    K/V (and k2) stay in HBM; each live page (table entry >= 0) is copied
    here, all kv heads in one DMA, into a double-buffered VMEM block. Key
    positions come from a lane-dense copy of the pool's positions held in
    VMEM, read per live page; dead pages read -1 and are masked."""
    if has_q2:
        q_ref, q2_ref, kpos_ref, *refs = refs
        hbm, (o_ref, *bufs) = refs[:3], refs[3:]         # k, v, k2
    else:
        q_ref, kpos_ref, *refs = refs
        hbm, (o_ref, *bufs) = refs[:2], refs[2:]         # k, v
    bufs, (sem, m_sc, l_sc, acc_sc) = bufs[:len(hbm)], bufs[len(hbm):]
    k_buf, v_buf = bufs[:2]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    slot = j % 2
    T = ppb * ps

    def page(jj, i):
        return tbl_ref[b, jj * ppb + i]

    def live(jj):
        out = page(jj, 0) >= 0
        for i in range(1, ppb):
            out |= page(jj, i) >= 0
        return out

    def each_page(jj, live_fn, dead_fn=None):
        def body(i, carry):
            pg = page(jj, i)
            rows = pl.ds(pl.multiple_of(i * ps, ps), ps)
            pl.when(pg >= 0)(lambda: live_fn(pg, rows))
            if dead_fn is not None:
                pl.when(pg < 0)(lambda: dead_fn(rows))
            return carry

        jax.lax.fori_loop(0, ppb, body, 0)

    def copies(buf_slot, act):
        def run(pg, rows):
            for n, (src, dst) in enumerate(zip(hbm, bufs)):
                act(pltpu.make_async_copy(src.at[pg], dst.at[buf_slot, :, rows],
                                          sem.at[n, buf_slot]))
        return run

    def zero_v(rows):                 # a hole keeps a stale block's bytes:
        v_buf[slot, :, rows] = jnp.zeros(  # zero its V so p = 0 meets zeros
            (v_buf.shape[1], ps, v_buf.shape[3]), v_buf.dtype)

    def key_positions():
        """(1, T) positions of block j's rows, -1 on dead pages: page pg's
        rows are lanes pg*ps.. of the lane-dense array, so each piece of
        min(ps, 128) lanes is one row load and one lane rotation."""
        n = min(ps, 128)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

        def piece(u, row):
            pg = page(j, u * n // ps)
            src = jnp.maximum(pg, 0) * ps + u * n % ps
            d = u * n % 128
            got = pltpu.roll(kpos_ref[pl.ds(src // 128, 1), :],
                             (d - src % 128) % 128, 1)
            keep = (lane >= d) & (lane < d + n) & (pg >= 0)
            return jnp.where(keep, got, row)

        per = 128 // n                                       # pieces a row
        rows = [jax.lax.fori_loop(c * per, min(c * per + per, T // n), piece,
                                  jnp.full((1, 128), -1, jnp.int32))
                for c in range(-(-T // 128))]
        return jnp.concatenate(rows, axis=1)[:, :T]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        pl.when(live(0))(lambda: each_page(0, copies(0, lambda c: c.start())))

    # the table carries one dead block past the last, so j + 1 is in range
    pl.when(live(j + 1))(
        lambda: each_page(j + 1, copies(1 - slot, lambda c: c.start())))

    @pl.when(live(j))
    def _block():
        each_page(j, copies(slot, lambda c: c.wait()), zero_v)
        kp = key_positions()                                 # (1, T)
        qp = qpos_ref[b]
        mask = kp >= 0
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= (qp - kp) < window
        nt = (((1,), (1,)), ((), ()))                        # a @ b.T
        for h in range(k_buf.shape[1]):                      # kv heads
            s = jax.lax.dot_general(q_ref[0, h], k_buf[slot, h], nt,
                                    preferred_element_type=jnp.float32)
            if has_q2:                                       # (G, T)
                s += jax.lax.dot_general(q2_ref[0, h], bufs[2][slot, h], nt,
                                         preferred_element_type=jnp.float32)
            if cap is not None:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[h]                                 # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_sc[h] = l_sc[h] * alpha + p.sum(axis=1, keepdims=True)
            m_sc[h] = m_new
            v = v_buf[slot, h]                               # (T, Dv)
            acc_sc[h] = acc_sc[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_sc[...]
        l = jnp.where(l > 0, l, 1.0)                         # dead slot -> 0
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _rows(x, scale, K: int, pad_q: int = 0):
    """(B, S, H, D) -> scaled (B, K, (S+pad_q)*G, D) rows grouped per kv
    head (row r = query r // G, head r % G of the group)."""
    B, S, H, D = x.shape
    G = H // K
    x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    x = x.reshape(B, S, K, G, D)
    if pad_q:
        x = jnp.pad(x, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3, 4).reshape(B, K, (S + pad_q) * G, D)


def _lanes(x):
    """``x`` with its minor dim zero-padded to a multiple of 128 lanes."""
    pad = -x.shape[-1] % 128
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _decode(q, k, v, kpos, tables, q_pos, q2, k2, *, scale, causal, window,
            softcap, interpret):
    """Decode form (S == 1): grid (slots, page blocks); ``_decode_kernel``."""
    Dv = v.shape[-1]
    # a page is copied whole, lanes included: a minor dim off the 128-lane
    # tile is zero-padded here (a copy of that leaf; exact: zeros add
    # nothing to q.k, and the padded V columns are cut off below)
    q, k, v = _lanes(q), _lanes(k), _lanes(v)
    if q2 is not None:
        q2, k2 = _lanes(q2), _lanes(k2)
    B, _, H, Dk = q.shape
    P, K, ps, _ = k.shape
    G = H // K
    npps = tables.shape[1]
    if 128 % ps and ps % 128:
        raise ValueError(f"page size {ps} neither divides nor is a multiple "
                         "of a 128-lane row")
    ppb = max(1, min(npps, DECODE_BLOCK_BYTES // (K * ps * Dk
                                                  * k.dtype.itemsize)))
    nj = -(-npps // ppb)
    T = ppb * ps
    # -1 past the last page: a ragged last block, and one dead block after
    # it that the last step's prefetch test reads
    tbl = jnp.pad(tables.astype(jnp.int32),
                  ((0, 0), (0, (nj + 1) * ppb - npps)), constant_values=-1)
    # positions lane-dense, 128 a row, whole in VMEM (4 bytes a pool row)
    flat = kpos.astype(jnp.int32).reshape(-1)
    nrows = -(-flat.size // 128)
    kpos_rows = jnp.pad(flat, (0, nrows * 128 - flat.size),
                        constant_values=-1).reshape(nrows, 128)

    def row_spec(d):
        return pl.BlockSpec((1, K, G, d), lambda b, j, tbl, qp: (b, 0, 0, 0))

    in_specs = [row_spec(Dk)]
    args = [_rows(q, scale, K)]
    if q2 is not None:
        in_specs.append(row_spec(q2.shape[-1]))
        args.append(_rows(q2, scale, K))
    in_specs.append(pl.BlockSpec((nrows, 128), lambda b, j, tbl, qp: (0, 0)))
    args.append(kpos_rows)
    pool = [k, v] + ([k2] if q2 is not None else [])
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pool)
    Dvp = v.shape[-1]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, ppb=ppb, ps=ps, causal=causal,
                          window=window, cap=softcap, has_q2=q2 is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nj),
            in_specs=in_specs,
            out_specs=row_spec(Dvp),
            scratch_shapes=[
                pltpu.VMEM((2, K, T, x.shape[-1]), x.dtype) for x in pool
            ] + [
                pltpu.SemaphoreType.DMA((len(pool), 2)),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, Dvp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dvp), v.dtype),
        name="paged_attention",
        interpret=interpret,
    )(tbl, q_pos[:, 0].astype(jnp.int32), *args, *pool)
    return out.reshape(B, 1, H, Dvp)[..., :Dv]


def paged_attention(q, k, v, kpos, tables, q_pos, *, q2=None, k2=None,
                    scale=None, causal: bool = True, window=None,
                    softcap=None, block_q: int = 128,
                    interpret: bool = False):
    """Attention over pool-resident KV via an in-kernel page-table walk.

    q:      (B, S, H, Dk)   queries (decode: S == 1)
    k:      (P, K, ps, Dk)  pooled keys, head-major — P pages of ps rows,
                            H % K == 0
    v:      (P, K, ps, Dv)  pooled values
    kpos:   (P, ps) int32   absolute position per pool row (-1 = empty)
    tables: (B, npps) int32 page table per slot (-1 = unallocated)
    q_pos:  (B, S) int32    absolute query positions (-1 = pad row)
    q2/k2:  optional second score component (MLA absorbed form);
            q2: (B, S, H, Dk2), k2: (P, K, ps, Dk2)

    At most ``block_q`` query positions form one grid step, fewer when
    ``block_q * H / K`` would pass ``MAX_ROWS`` rows.

    Returns (B, S, H, Dv) in v.dtype. A slot whose table is all -1 (or a
    pad query row) gets exact zeros.
    """
    B, S, H, Dk = q.shape
    P, K, ps, _ = k.shape
    Dv = v.shape[-1]
    if H % K:
        raise ValueError(f"{H} query heads do not group over {K} kv heads")
    G = H // K
    npps = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dk + (q2.shape[-1] if q2 is not None else 0))

    if S == 1:
        return _decode(q, k, v, kpos, tables, q_pos, q2, k2, scale=scale,
                       causal=causal, window=window, softcap=softcap,
                       interpret=interpret)

    bq = min(block_q, S, max(1, MAX_ROWS // G))
    if bq < S:                      # a partial block's rows must tile by 8
        m = 8 // math.gcd(G, 8)
        bq = max(m, bq - bq % m)
    pad_q = (-S) % bq
    Sp = S + pad_q
    nq = Sp // bq
    R = bq * G                                   # query rows per grid step

    def rows(x):
        return _rows(x, scale, K, pad_q)

    if pad_q:
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-1)
    qp_rows = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[..., None]
    grid = (B, K, nq, npps)

    def _page(b, j, tbl):
        return jnp.maximum(tbl[b, j], 0)       # -1 clamps; masked in-kernel

    def row_spec(d):
        return pl.BlockSpec((1, 1, R, d), lambda b, h, i, j, tbl: (b, h, i, 0))

    def page_spec(d):
        return pl.BlockSpec((1, 1, ps, d),
                            lambda b, h, i, j, tbl: (_page(b, j, tbl), h, 0, 0))

    in_specs = [
        row_spec(Dk), page_spec(Dk), page_spec(Dv),
        pl.BlockSpec((1, 1, ps),
                     lambda b, h, i, j, tbl: (_page(b, j, tbl), 0, 0)),
        pl.BlockSpec((1, R, 1), lambda b, h, i, j, tbl: (b, i, 0)),
    ]
    args = [rows(q), k, v, kpos.reshape(P, 1, ps), qp_rows]
    if q2 is not None:
        in_specs += [row_spec(q2.shape[-1]), page_spec(k2.shape[-1])]
        args += [rows(q2), k2]

    out = pl.pallas_call(
        functools.partial(_kernel, causal=causal, window=window, cap=softcap,
                          has_q2=q2 is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=row_spec(Dv),
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, Sp * G, Dv), v.dtype),
        name="paged_attention",
        interpret=interpret,
    )(tables, *args)
    out = out.reshape(B, K, Sp, G, Dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sp, H, Dv)[:, :S]
