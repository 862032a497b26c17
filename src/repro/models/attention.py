"""Attention: GQA (qk-norm / softcap / sliding window / cross) and MLA.

One flat-head core serves every variant:
  * GQA     -> kv heads repeated up to H before the einsum (the repeat is a
               local slice under SPMD when q-heads are sharded over `model`)
  * MLA     -> prefill uses the decompressed (full) form; decode uses the
               weight-absorbed form, which is exactly MQA against the
               compressed cache (K=1, asymmetric qk/v dims)
  * cross   -> encoder keys/values, non-causal

Two execution paths, chosen by static shape:
  * flat    -> materialized (B,H,Q,S) logits (small S)
  * blocked -> lax.scan over key blocks with online softmax (flash-style);
               bounds live memory at O(Q x block) for 32k/500k sequences.
               The Pallas kernel in ``repro.kernels.flash_attention`` is the
               TPU-native version of this path.

Caches are fixed-capacity ring buffers ``{k, v, pos}`` where ``pos`` holds the
absolute position stored in each slot (-1 = empty). Softmax is permutation
invariant, so ring order never matters; masks derive from ``pos`` alone.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, fan_in_init, head_rmsnorm, softcap
from repro.models.spec import AttentionSpec, ModelConfig
from repro.sharding.partition import constrain

# Blocked (online-softmax) path only above this key length: at 4k the flat
# path is cheaper on the traffic instrument (fewer scan-machinery copies);
# at 32k+ flat logits don't fit. Measured both ways (EXPERIMENTS.md §Perf).
BLOCKED_THRESHOLD = 8192
KV_BLOCK = 1024            # key-block width for the blocked path

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Core (flat heads, asymmetric qk/v dims)
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, causal, window):
    """q_pos: (B,Q), k_pos: (B,S) -> bool (B,Q,S). Empty slots have pos=-1."""
    valid = (k_pos >= 0)[:, None, :]
    if causal:
        valid &= k_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        valid &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    return valid


def attn_core(q, k, v, q_pos, k_pos, *, scale, causal, window, cap,
              n_kv: int, prefer_blocked: bool = False):
    """Grouped GQA core — kv is NEVER repeated to H (no (B,S,H,D) blowup).

    q: (B,Q,H,Dk) with H = n_kv*G;  k: (B,S,K,Dk);  v: (B,S,K,Dv).
    Returns (B,Q,H,Dv)."""
    B, Q, H, Dk = q.shape
    G = H // n_kv
    q5 = q.reshape(B, Q, n_kv, G, Dk)
    # batch follows the CACHE's batch sharding (cache_batch), so decode
    # logits (B,K,G,1,S) shard over batch x seq instead of replicating —
    # un-pinned, internvl2 decode_32k carried a 10.7 GB replicated logits
    # buffer per chip
    q5 = constrain(q5, "cache_batch", "seq", "kv_heads", "q_group",
                   "head_dim")
    # Decode (Q==1) ALWAYS takes the flat path: logits are (B,H,S) — tiny
    # per chip when the cache is seq-sharded — and GSPMD turns the softmax
    # over the sharded S into scalar-sized stat all-reduces. The blocked
    # scan would instead iterate every global block on every chip, forcing
    # a full f32 all-gather of the cache (measured 4.8e11 B/chip/token).
    blocked = (Q > 1 and
               k.shape[1] > (KV_BLOCK if prefer_blocked else BLOCKED_THRESHOLD))
    if blocked:
        out = _attn_blocked(q5, k, v, q_pos, k_pos, scale=scale,
                            causal=causal, window=window, cap=cap)
    else:
        out = _attn_flat(q5, k, v, q_pos, k_pos, scale=scale, causal=causal,
                         window=window, cap=cap)
    # pin the output to the SAME 5D layout as q5 — a divergent constraint
    # here (e.g. heads-sharded out vs seq-sharded q) makes GSPMD all-gather
    # f32 logits inside the kv scan (measured +55s collective on internvl2)
    out = constrain(out, "cache_batch", "seq", "kv_heads", "q_group",
                    "head_dim")
    return out.reshape(B, Q, H, v.shape[-1])


def _attn_flat(q, k, v, q_pos, k_pos, *, scale, causal, window, cap):
    """q: (B,Q,K,G,Dk), k: (B,S,K,Dk), v: (B,S,K,Dv) -> (B,Q,K,G,Dv)."""
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)   # fold scale into q
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32)
    if cap is not None:
        s = softcap(s, cap)
    m = _mask(q_pos, k_pos, causal, window)[:, None, None]  # (B,1,1,Q,S)
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (e.g. empty ring slots only) -> zeros, not NaN
    p = jnp.where(m.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bkgqs,bskv->bqkgv", p.astype(v.dtype), v)


def _attn_blocked(q, k, v, q_pos, k_pos, *, scale, causal, window, cap):
    """Online-softmax scan over key blocks (jnp flash; O(Q x block) memory).

    q: (B,Q,K,G,Dk); k/v stay at K kv-heads throughout."""
    B, Q, K, G, Dk = q.shape
    S = k.shape[1]
    Dv = v.shape[-1]
    nb = -(-S // KV_BLOCK)
    pad = nb * KV_BLOCK - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)

    kb = k.reshape(B, nb, KV_BLOCK, K, Dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, KV_BLOCK, K, Dv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(B, nb, KV_BLOCK).transpose(1, 0, 2)

    # fold the softmax scale into q once, outside the kv scan — saves a full
    # f32 pass over the logits per block (measured 1.6e12 B/chip on
    # deepseek train_4k)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def step(carry, xs):
        m_run, l_run, acc = carry
        kc, vc, pc = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, kc,
                       preferred_element_type=jnp.float32)
        if cap is not None:
            s = softcap(s, cap)
        msk = _mask(q_pos, pc, causal, window)[:, None, None]
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        # exp(NEG_INF - m_new) underflows to exactly 0 for any real m_new,
        # so the masked-out entries need no second `where` pass (rows with
        # zero valid keys cannot occur: causal rows always see themselves,
        # ring slots are never all-empty, encoders are unmasked)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        # p in bf16 for the pv matmul with f32 accumulation — the MXU-native
        # form; also stops XLA hoisting a full f32 copy of the v cache out
        # of the loop (measured 1.4e12 B/chip on qwen3 decode_32k)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskv->bkgqv", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, K, G, Q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Q), jnp.float32)
    a0 = jnp.zeros((B, K, G, Q, Dv), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kb, vb, pb))
    l_safe = jnp.where(l_f > 0, l_f, 1.0)
    out = acc / l_safe[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, d_model: int, spec: AttentionSpec, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    H, K, Dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    dt = jnp.bfloat16
    p = {
        "wq": fan_in_init(ks[0], (d_model, H, Dh), d_model, dt),
        "wk": fan_in_init(ks[1], (d_model, K, Dh), d_model, dt),
        "wv": fan_in_init(ks[2], (d_model, K, Dh), d_model, dt),
        "wo": fan_in_init(ks[3], (H, Dh, d_model), H * Dh, dt),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((H, Dh), dt)
        p["bk"] = jnp.zeros((K, Dh), dt)
        p["bv"] = jnp.zeros((K, Dh), dt)
        p["bo"] = jnp.zeros((d_model,), dt)
    if spec.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dt)
        p["k_norm"] = jnp.ones((Dh,), dt)
    return p


_CACHE_AXES = {
    "k": ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
    "v": ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
    "k_scale": ("cache_batch", "cache_seq", "kv_heads"),
    "v_scale": ("cache_batch", "cache_seq", "kv_heads"),
    "pos": ("cache_batch", "cache_seq"),
    "ckv": ("cache_batch", "cache_seq", "kv_lora"),
    "kr": ("cache_batch", "cache_seq", "head_dim"),
}


def _kv_quantize(x: jax.Array):
    """Per-(token,head) symmetric int8. x: (B,S,K,D) -> (int8, scale bf16)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _kv_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.bfloat16) * scale[..., None].astype(jnp.bfloat16))


def constrain_cache(cache: dict) -> dict:
    """Pin cache tensors to their layout so scan-collected cache outputs are
    never replicated by sharding propagation (a 10x+ memory trap)."""
    return {k: constrain(v, *_CACHE_AXES[k]) if k in _CACHE_AXES else v
            for k, v in cache.items()}


def _ring_update(cache: dict, new: dict, positions: jax.Array) -> dict:
    """Write new entries into ring slots pos % capacity.

    Handles: decode (one token), prefill shorter than capacity (contiguous
    block starting at slot 0), and prefill LONGER than a windowed layer's
    capacity (keep the trailing window; a full-coverage write realized as a
    roll so every row lands on its pos%cap slot)."""
    cap = cache["pos"].shape[1]
    S = positions.shape[1]
    entries = dict(new)
    entries["pos"] = positions
    if S >= cap:
        sliced = {k: v[:, -cap:] for k, v in entries.items()}
        shift = sliced["pos"][:, 0] % cap
        return constrain_cache(
            {k: jax.vmap(lambda a, s: jnp.roll(a, s, axis=0))(v, shift)
             for k, v in sliced.items()})
    slot = positions[:, 0] % cap                                # (B,)
    return constrain_cache({k: jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, 0)
    )(cache[k], entries[k], slot) for k in cache})


# ---------------------------------------------------------------------------
# Paged leaf path (pool-resident caches; serve engine)
# ---------------------------------------------------------------------------
#
# A paged cache leaf is the POOL's leaf for one scan repeat plus the slots'
# page tables: {"k": (P, K, ps, D), "v": ..., "pos": (P, ps),
# "table": (B, npps)} (MLA: ckv/kr instead of k/v; int8: + k_scale/v_scale).
# Fresh rows are scattered straight into their pages (no dense intermediate)
# and attention reads the pool through the table — either by materializing
# this one leaf's dense view (cfg.paged_kernel == "gather", the XLA
# baseline) or by walking the table inside the Pallas kernel ("pallas").
# Row -> page mapping matches ``models.lm.paged_scatter``: virtual row
# v = pos % vcap lives in page table[v // ps] at offset v % ps; a -1 table
# entry (stalled/dead slot) or -1 position (pad row) drops the write via an
# out-of-range page index. Leaves with a kv-head axis are stored head-major,
# (P, K, ps, ...), so one page of one kv head is the contiguous (ps, D)
# tile the kernel loads; the others (pos, MLA's ckv/kr) are (P, ps, ...).

HEAD_MAJOR = frozenset({"k", "v", "k_scale", "v_scale"})

def _paged_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _paged_leaf_update(cache: dict, entries: dict,
                       positions: jax.Array) -> dict:
    """Scatter fresh (B,S) rows into the pool pages mapped by the table."""
    table = cache["table"]                                    # (B, npps)
    P, ps = cache["pos"].shape
    vcap = table.shape[1] * ps
    valid = positions >= 0
    v = jnp.where(valid, positions % vcap, 0)
    page = jnp.take_along_axis(table, v // ps, axis=1)        # (B, S)
    off = v % ps
    tgt = jnp.where(valid & (page >= 0), page, P)             # OOB drops
    new = dict(cache)
    for k, rows in entries.items():
        idx = (tgt, slice(None), off) if k in HEAD_MAJOR else (tgt, off)
        new[k] = cache[k].at[idx].set(rows.astype(cache[k].dtype),
                                      mode="drop")
    new["pos"] = cache["pos"].at[tgt, off].set(positions, mode="drop")
    return new


def _paged_leaf_gather(cache: dict):
    """Dense per-slot view of ONE pool leaf: ({k: (B,vcap,...)}, kpos)."""
    table = cache["table"]
    ps = cache["pos"].shape[-1]
    B, npps = table.shape
    cl = jnp.maximum(table, 0)

    def g(leaf, head_major=False):
        d = jnp.take(leaf, cl, axis=0)            # (B, npps, [K,] ps, ...)
        if head_major:
            d = jnp.swapaxes(d, 2, 3)
        return d.reshape(B, npps * ps, *d.shape[3:])

    dense = {k: g(v, k in HEAD_MAJOR) for k, v in cache.items()
             if k not in ("table", "pos")}
    kpos = jnp.where(jnp.repeat(table >= 0, ps, axis=1), g(cache["pos"]), -1)
    return dense, kpos


def _paged_gqa(params: dict, cache: dict, q, k, v, spec: AttentionSpec,
               cfg: ModelConfig, positions: jax.Array):
    """GQA over a paged leaf: scatter fresh rows, attend through the table.

    int8-quantized leaves always take the gather impl (the kernel reads
    raw pool leaves and does not dequantize in-kernel)."""
    quant = "k_scale" in cache
    if quant:
        kq, ks = _kv_quantize(k)
        vq, vs = _kv_quantize(v)
        new_cache = _paged_leaf_update(
            cache, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs},
            positions)
    else:
        new_cache = _paged_leaf_update(cache, {"k": k, "v": v}, positions)
    scale = 1.0 / (spec.head_dim ** 0.5)
    if cfg.paged_kernel == "pallas" and not quant:
        from repro.kernels.paged_attention import paged_attention
        out = paged_attention(
            q, new_cache["k"], new_cache["v"], new_cache["pos"],
            new_cache["table"], positions, scale=scale, causal=spec.causal,
            window=spec.window, softcap=spec.logit_softcap,
            interpret=_paged_interpret())
    else:
        dense, kpos = _paged_leaf_gather(new_cache)
        if quant:
            kd = _kv_dequantize(dense["k"], dense["k_scale"])
            vd = _kv_dequantize(dense["v"], dense["v_scale"])
        else:
            kd, vd = dense["k"], dense["v"]
        out = attn_core(q, kd, vd, positions, kpos, scale=scale,
                        causal=spec.causal, window=spec.window,
                        cap=spec.logit_softcap, n_kv=kd.shape[2])
    return out, new_cache


def gqa_apply(params: dict, x: jax.Array, spec: AttentionSpec,
              cfg: ModelConfig, positions: jax.Array,
              cache: Optional[dict] = None,
              encoder_out: Optional[dict] = None):
    """x: (B,S,D). Returns (y, new_cache)."""
    H, K, Dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    B, S, _ = x.shape

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]

    if encoder_out is not None:                  # cross-attention: static kv
        k, v = encoder_out["k"], encoder_out["v"]
        k_pos = jnp.zeros(k.shape[:2], jnp.int32)
        if spec.qk_norm:
            q = head_rmsnorm(params["q_norm"], q)
        new_cache = cache
        causal, window = False, None
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        if "bk" in params:
            k, v = k + params["bk"], v + params["bv"]
        if spec.qk_norm:
            q = head_rmsnorm(params["q_norm"], q)
            k = head_rmsnorm(params["k_norm"], k)
        if spec.rope_theta:
            q = apply_rope(q, positions, spec.rope_theta, spec.rope_pct)
            k = apply_rope(k, positions, spec.rope_theta, spec.rope_pct)
        causal, window = spec.causal, spec.window

        if cache is not None and "table" in cache:   # paged pool leaf
            out, new_cache = _paged_gqa(params, cache, q, k, v, spec, cfg,
                                        positions)
            y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
            if "bo" in params:
                y = y + params["bo"]
            return y, new_cache

        if cache is not None:
            if "k_scale" in cache:             # int8 KV cache
                kq, ks = _kv_quantize(k)
                vq, vs = _kv_quantize(v)
                new_cache = _ring_update(
                    cache, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs},
                    positions)
            else:
                new_cache = _ring_update(cache, {"k": k, "v": v}, positions)
            if S == 1:
                # decode: attend the ring contents
                if "k_scale" in new_cache:
                    k = _kv_dequantize(new_cache["k"], new_cache["k_scale"])
                    v = _kv_dequantize(new_cache["v"], new_cache["v_scale"])
                else:
                    k, v = new_cache["k"], new_cache["v"]
                k_pos = new_cache["pos"]
            else:
                # prefill: attend the fresh full-sequence k/v — early queries
                # need history a windowed ring no longer holds
                k_pos = positions
        else:
            k_pos = positions
            new_cache = None

    out = attn_core(q, k, v, positions, k_pos,
                    scale=1.0 / (Dh ** 0.5), causal=causal,
                    window=window, cap=spec.logit_softcap,
                    n_kv=k.shape[2])
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if "bo" in params:
        y = y + params["bo"]
    return y, new_cache


def gqa_encoder_kv(params: dict, enc: jax.Array, spec: AttentionSpec) -> dict:
    """Precompute cross-attention k/v from encoder output (no rope)."""
    k = jnp.einsum("bsd,dhk->bshk", enc, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc, params["wv"])
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    return {"k": k, "v": v}


def gqa_cache_init(batch: int, capacity: int, spec: AttentionSpec) -> dict:
    K, Dh = spec.n_kv_heads, spec.head_dim
    cap = capacity if spec.window is None else min(capacity, spec.window)
    kv_dt = jnp.int8 if spec.kv_quant else jnp.bfloat16
    c = {
        "k": jnp.zeros((batch, cap, K, Dh), kv_dt),
        "v": jnp.zeros((batch, cap, K, Dh), kv_dt),
        "pos": jnp.full((batch, cap), -1, jnp.int32),
    }
    if spec.kv_quant:
        c["k_scale"] = jnp.zeros((batch, cap, K), jnp.bfloat16)
        c["v_scale"] = jnp.zeros((batch, cap, K), jnp.bfloat16)
    return c


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, d_model: int, spec: AttentionSpec, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    H = spec.n_heads
    ql, kl = spec.q_lora_rank, spec.kv_lora_rank
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    dt = jnp.bfloat16
    return {
        "w_dq": fan_in_init(ks[0], (d_model, ql), d_model, dt),
        "q_norm": jnp.ones((ql,), dt),
        "w_uq": fan_in_init(ks[1], (ql, H, dn + dr), ql, dt),
        "w_dkv": fan_in_init(ks[2], (d_model, kl + dr), d_model, dt),
        "kv_norm": jnp.ones((kl,), dt),
        "w_uk": fan_in_init(ks[3], (kl, H, dn), kl, dt),
        "w_uv": fan_in_init(ks[4], (kl, H, dv), kl, dt),
        "wo": fan_in_init(ks[5], (H, dv, d_model), H * dv, dt),
    }


def mla_cache_init(batch: int, capacity: int, spec: AttentionSpec) -> dict:
    return {
        "ckv": jnp.zeros((batch, capacity, spec.kv_lora_rank), jnp.bfloat16),
        "kr": jnp.zeros((batch, capacity, spec.qk_rope_head_dim), jnp.bfloat16),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
    }


def _mla_compress(params, x, spec, positions):
    """x -> (ckv (B,S,kl) normalized, kr (B,S,dr) roped)."""
    kl = spec.kv_lora_rank
    dkv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"])
    ckv, kr = dkv[..., :kl], dkv[..., kl:]
    ckv = head_rmsnorm(params["kv_norm"], ckv)
    kr = apply_rope(kr, positions, spec.rope_theta)
    return ckv, kr


def _mla_queries(params, x, spec, positions):
    dn = spec.qk_nope_head_dim
    cq = jnp.einsum("bsd,dq->bsq", x, params["w_dq"])
    cq = head_rmsnorm(params["q_norm"], cq)
    q = jnp.einsum("bsq,qhk->bshk", cq, params["w_uq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, spec.rope_theta)
    return q_nope, q_rope


def mla_apply(params: dict, x: jax.Array, spec: AttentionSpec,
              cfg: ModelConfig, positions: jax.Array,
              cache: Optional[dict] = None,
              encoder_out: Optional[dict] = None):
    B, S, _ = x.shape
    H = spec.n_heads
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    scale = 1.0 / ((dn + dr) ** 0.5)

    q_nope, q_rope = _mla_queries(params, x, spec, positions)
    ckv, kr = _mla_compress(params, x, spec, positions)

    if cache is not None and "table" in cache:   # paged pool leaf
        # weight-absorbed form for ANY S: MQA against the compressed pool
        # (exact — scores q_abs.ckv + q_rope.kr, values ckv @ W_uv), so a
        # warm-prefix suffix prefill attends shared pages directly
        new_cache = _paged_leaf_update(cache, {"ckv": ckv, "kr": kr},
                                       positions)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])
        if cfg.paged_kernel == "pallas":
            from repro.kernels.paged_attention import paged_attention
            ckv_p = new_cache["ckv"][:, None].astype(q_abs.dtype)
            ctx = paged_attention(
                q_abs, ckv_p, ckv_p,
                new_cache["pos"], new_cache["table"], positions,
                q2=q_rope, k2=new_cache["kr"][:, None].astype(q_abs.dtype),
                scale=scale, causal=True, interpret=_paged_interpret())
        else:
            dense, k_pos = _paged_leaf_gather(new_cache)
            q_cat = jnp.concatenate([q_abs, q_rope], axis=-1)
            k_cat = jnp.concatenate([dense["ckv"], dense["kr"]], axis=-1)
            ctx = attn_core(q_cat, k_cat[:, :, None, :].astype(q_cat.dtype),
                            dense["ckv"][:, :, None, :].astype(q_cat.dtype),
                            positions, k_pos, scale=scale, causal=True,
                            window=None, cap=None, n_kv=1)
        out = jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"])
        y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
        return y, new_cache

    if cache is not None and S == 1:
        # ---- decode: weight-absorbed form == MQA over the compressed cache
        cache = _ring_update(cache, {"ckv": ckv, "kr": kr}, positions)
        k_pos = cache["pos"]
        # absorb W_uk into q:  (B,1,H,dn) x (kl,H,dn) -> (B,1,H,kl)
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, params["w_uk"])
        q_cat = jnp.concatenate([q_abs, q_rope], axis=-1)       # (B,1,H,kl+dr)
        k_cat = jnp.concatenate([cache["ckv"], cache["kr"]], axis=-1)
        ctx = attn_core(q_cat, k_cat[:, :, None, :].astype(q_cat.dtype),
                        cache["ckv"][:, :, None, :].astype(q_cat.dtype),
                        positions, k_pos, scale=scale, causal=True,
                        window=None, cap=None, n_kv=1)           # (B,1,H,kl)
        out = jnp.einsum("bshr,rhv->bshv", ctx, params["w_uv"])
        y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
        return y, cache

    # ---- train / prefill: full (decompressed) form
    if cache is not None:
        cache = dict(cache)
        cache["ckv"] = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, 0, 1)
        cache["kr"] = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr, 0, 1)
        cache["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions, 0, 1)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uk"])
    v = jnp.einsum("bsr,rhv->bshv", ckv, params["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kr[:, :, None, :], (B, S, H, dr)).astype(k_nope.dtype)], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attn_core(q, k, v, positions, positions, scale=scale,
                    causal=True, window=None, cap=spec.logit_softcap,
                    n_kv=H, prefer_blocked=spec.prefer_blocked)
    y = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
    return y, cache


# ---------------------------------------------------------------------------

def attn_init(key, d_model, spec, cfg):
    return mla_init(key, d_model, spec, cfg) if spec.kind == "mla" \
        else gqa_init(key, d_model, spec, cfg)


def attn_apply(params, x, spec, cfg, positions, cache=None, encoder_out=None):
    fn = mla_apply if spec.kind == "mla" else gqa_apply
    return fn(params, x, spec, cfg, positions, cache=cache,
              encoder_out=encoder_out)


def attn_cache_init(batch, capacity, spec):
    return mla_cache_init(batch, capacity, spec) if spec.kind == "mla" \
        else gqa_cache_init(batch, capacity, spec)
