"""Full models: decoder-only LM (dense/MoE/SSM/hybrid/VLM) and enc-dec.

Public surface (all pure functions — the Wine ABI wraps exactly these):
  lm_init(key, cfg)                                  -> params
  lm_hidden(params, inputs, cfg, caches=None, ...)   -> (hidden, caches, aux)
  lm_logits(params, hidden, cfg)                     -> logits
  lm_loss(params, batch, cfg, remat=True)            -> (loss, metrics)
  prefill(params, inputs, cfg, capacity)             -> (last_logits, caches)
  prefill_batched(params, inputs, cfg, lengths, ...) -> (last_logits, caches)
  decode_step(params, caches, tokens, pos, cfg)      -> (logits, caches)
  cache_init(cfg, batch, capacity)                   -> caches

Paged KV (the shared-pool serving path — ``repro.serve``):
  paged_cache_init(cfg, slots, n_pages, page_size)   -> pool caches
  paged_gather(pool, tables)                         -> dense per-slot caches
  paged_scatter(pool, dense, tables, claim, ...)     -> pool caches
  paged_clear(pool, page_ids)                        -> pool caches
  paged_prefill(params, pool, tables, tokens, ...)   -> (logits, pool)
  paged_decode_step(params, pool, tables, t, p, cfg) -> (logits, pool)
  count_params(cfg, active_only=False)               -> int
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.attention import HEAD_MAJOR, attn_cache_init
from repro.models.blocks import (block_cache_init, group_apply,
                                 group_cache_init, group_init)
from repro.models.ssm import ssm_cache_init
from repro.models.layers import (embed_init, embed_logits, embed_lookup,
                                 norm_apply, norm_init, normal_init, softcap)
from repro.models.spec import ModelConfig
from repro.sharding.partition import constrain

LOSS_CHUNK = 512          # sequence chunk for the vocab-sharded CE loss
IGNORE = -100


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def lm_init(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4 + len(cfg.groups))
    dt = jnp.bfloat16
    p: dict = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dt),
        "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.use_bias, dt),
        "groups": [group_init(ks[4 + i], cfg, g)
                   for i, g in enumerate(cfg.groups)],
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = {"lm_head": normal_init(
            ks[1], (cfg.d_model, cfg.vocab), 0.02, dt)}
    if cfg.learned_pos:
        p["pos"] = {"pos_embed": normal_init(
            ks[2], (cfg.max_pos, cfg.d_model), 0.02, dt)}
    if cfg.encoder is not None:
        enc = cfg.encoder
        eks = jax.random.split(ks[3], 2 + len(enc.groups))
        p["encoder"] = {
            "groups": [group_init(eks[2 + i], cfg, g)
                       for i, g in enumerate(enc.groups)],
            "final_norm": norm_init(cfg.d_model, cfg.norm, cfg.use_bias, dt),
            "pos": {"pos_embed": normal_init(
                eks[0], (enc.seq_len, cfg.d_model), 0.02, dt)},
        }
    return p


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def encoder_apply(params: dict, frames: jax.Array, cfg: ModelConfig,
                  remat: bool = False) -> jax.Array:
    """frames: (B, S_enc, D) stubbed frontend embeddings."""
    enc = params["encoder"]
    x = frames + enc["pos"]["pos_embed"][None, : frames.shape[1]]
    x = constrain(x, "batch", "seq", "act_d")
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1], dtype=jnp.int32)[None],
                           frames.shape[:2])
    for gi, g in enumerate(cfg.encoder.groups):
        x, _, _ = group_apply(enc["groups"][gi], x, g, cfg, pos, remat=remat)
    return norm_apply(enc["final_norm"], x, cfg.norm, cfg.norm_eps)


def _embed_inputs(params, inputs, cfg):
    tokens = inputs["tokens"]
    x = embed_lookup(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    if cfg.frontend == "vlm_patch" and "embeds" in inputs:
        x = jnp.concatenate([inputs["embeds"].astype(x.dtype), x], axis=1)
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32)[None], x.shape[:2])
    if cfg.learned_pos:
        x = x + jnp.take(params["pos"]["pos_embed"], positions, axis=0)
    return x, positions


def lm_hidden(params: dict, inputs: dict, cfg: ModelConfig,
              caches: Optional[list] = None, enc_out: Optional[jax.Array] = None,
              remat: bool = False):
    """Returns (hidden, new_caches, aux)."""
    x, positions = _embed_inputs(params, inputs, cfg)
    x = constrain(x, "batch", "seq", "act_d")
    aux = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for gi, g in enumerate(cfg.groups):
        c = caches[gi] if caches is not None else None
        x, nc, a = group_apply(params["groups"][gi], x, g, cfg, positions,
                               caches=c, enc_out=enc_out, remat=remat)
        aux = aux + a
        if new_caches is not None:
            new_caches.append(nc)
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return x, new_caches, aux


def lm_logits(params: dict, hidden: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = embed_logits(params["embed"], hidden)
    else:
        logits = jnp.einsum("...d,dv->...v", hidden,
                            params["lm_head"]["lm_head"])
    if cfg.final_logit_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Loss (chunked over sequence — never materializes (B,S,V) at once)
# ---------------------------------------------------------------------------

def _ce_chunk(params, h, labels, cfg):
    logits = lm_logits(params, h, cfg).astype(jnp.float32)
    mask = labels != IGNORE
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = jnp.where(mask, lse - gold, 0.0)
    return ce.sum(), mask.sum()


def lm_loss(params: dict, batch: dict, cfg: ModelConfig, remat: bool = True,
            enc_out: Optional[jax.Array] = None):
    """batch: {tokens (B,S), labels (B,S), [embeds], [frames]}."""
    if cfg.encoder is not None and enc_out is None:
        enc_out = encoder_apply(params, batch["frames"], cfg, remat=remat)
    h, _, aux = lm_hidden(params, batch, cfg, enc_out=enc_out, remat=remat)
    labels = batch["labels"]
    if cfg.frontend == "vlm_patch" and "embeds" in batch:
        pad = jnp.full(batch["embeds"].shape[:2], IGNORE, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)

    S = h.shape[1]
    chunk = min(LOSS_CHUNK, S)
    if S % chunk == 0 and S > chunk:
        n = S // chunk
        hc = h.reshape(h.shape[0], n, chunk, -1).transpose(1, 0, 2, 3)
        lc = labels.reshape(labels.shape[0], n, chunk).transpose(1, 0, 2)

        def body(carry, xs):
            tot, cnt = carry
            hh, ll = xs
            s, c = _ce_chunk(params, hh, ll, cfg)
            return (tot + s, cnt + c), None

        (tot, cnt), _ = jax.lax.scan(
            jax.checkpoint(body) if remat else body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (hc, lc))
    else:
        tot, cnt = _ce_chunk(params, h, labels, cfg)
    ce = tot / jnp.maximum(cnt, 1)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def cache_init(cfg: ModelConfig, batch: int, capacity: int) -> list:
    return [group_cache_init(cfg, g, batch, capacity) for g in cfg.groups]


def prefill(params: dict, inputs: dict, cfg: ModelConfig,
            enc_out: Optional[jax.Array] = None,
            capacity: Optional[int] = None):
    """Full-sequence forward; returns (last-token logits, filled caches).

    ``capacity`` sizes the KV ring buffers (>= prompt + planned decode
    length); defaults to the prompt length.
    """
    x, positions = _embed_inputs(params, inputs, cfg)
    x = constrain(x, "batch", "seq", "act_d")
    B, S = x.shape[:2]
    capacity = max(capacity or S, S)
    caches = []
    for gi, g in enumerate(cfg.groups):
        c = group_cache_init(cfg, g, B, capacity)
        x, nc, _ = group_apply(params["groups"][gi], x, g, cfg, positions,
                               caches=c, enc_out=enc_out)
        caches.append(nc)
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = lm_logits(params, x[:, -1:], cfg)
    return logits, caches


def decode_step(params: dict, caches: list, tokens: jax.Array,
                pos: jax.Array, cfg: ModelConfig,
                enc_out: Optional[jax.Array] = None):
    """tokens: (B,1) int32, pos: (B,1) absolute position. One new token."""
    inputs = {"tokens": tokens, "positions": pos}
    h, new_caches, _ = lm_hidden(params, inputs, cfg, caches=caches,
                                 enc_out=enc_out)
    logits = lm_logits(params, h, cfg)
    return logits, new_caches


def _map_attn_subs(caches: list, attn_fn, ssm_fn=None):
    """Walk a cache pytree (list of group trees of block dicts) applying
    ``attn_fn`` to every attention sub-cache and ``ssm_fn`` (identity when
    None) to every SSM sub-cache. Preserves structure."""
    out = []
    for gtree in caches:
        ng = {}
        for bi, btree in gtree.items():
            nb = {}
            for kind, sub in btree.items():
                if kind == "attn":
                    nb[kind] = attn_fn(sub)
                else:
                    nb[kind] = ssm_fn(sub) if ssm_fn is not None else sub
            ng[bi] = nb
        out.append(ng)
    return out


def _zip_attn_subs(pool: list, dense: list, attn_fn, ssm_fn):
    """Two-tree variant of ``_map_attn_subs`` (pool and dense in lockstep)."""
    out = []
    for gpool, gdense in zip(pool, dense):
        ng = {}
        for bi in gpool:
            nb = {}
            for kind in gpool[bi]:
                fn = attn_fn if kind == "attn" else ssm_fn
                nb[kind] = fn(gpool[bi][kind], gdense[bi][kind])
            ng[bi] = nb
        out.append(ng)
    return out


def prefill_batched(params: dict, inputs: dict, cfg: ModelConfig,
                    lengths: jax.Array,
                    enc_out: Optional[jax.Array] = None,
                    capacity: Optional[int] = None):
    """Multi-slot prefill of right-padded prompts in ONE executable.

    ``inputs["tokens"]`` is (B, S) with row b's real prompt in columns
    ``[0, lengths[b])`` and arbitrary padding after. Causality means pad
    columns (later positions) never influence real tokens, so each row's
    last-real-token logits equal the unpadded single-prompt prefill.
    Returns (per-row last-REAL-token logits (B, 1, V), caches with every
    pad entry neutralized — ``pos`` forced to -1 — so a later decode can
    never attend padding).

    NOTE: only valid for attention-cached models. SSM/conv state is a
    recurrence over ALL processed tokens including pads; callers batching
    prompts for an SSM-bearing config must group by exact length (no pads).
    """
    x, positions = _embed_inputs(params, inputs, cfg)
    x = constrain(x, "batch", "seq", "act_d")
    B, S = x.shape[:2]
    capacity = max(capacity or S, S)
    caches = []
    for gi, g in enumerate(cfg.groups):
        c = group_cache_init(cfg, g, B, capacity)
        x, nc, _ = group_apply(params["groups"][gi], x, g, cfg, positions,
                               caches=c, enc_out=enc_out)
        caches.append(nc)
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, S - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)      # (B,1,D)
    logits = lm_logits(params, last, cfg)
    lim = lengths.astype(jnp.int32)[None, :, None]                 # (1,B,1)

    def neutralize(sub):
        sub = dict(sub)
        p = sub["pos"]
        sub["pos"] = jnp.where((p >= 0) & (p < lim), p, -1)
        return sub

    return logits, _map_attn_subs(caches, neutralize)


# ---------------------------------------------------------------------------
# Paged KV: one shared page pool, per-slot page tables
# ---------------------------------------------------------------------------
#
# Dense serving statically partitions KV capacity: ``cache_init(cfg, slots,
# capacity)`` gives every slot its own ring whether it holds an 8-token or
# an 800-token request. The paged layout pools that memory: attention cache
# leaves carry a PAGE axis of ``n_pages`` fixed-size pages — (repeats,
# n_pages, page_size, ...), head-major (repeats, n_pages, kv_heads,
# page_size, ...) for the k/v leaves (``attention.HEAD_MAJOR``) — and each
# slot owns an ordered page list (its
# page table). Slot b's virtual cache row v lives in page
# ``tables[b, v // page_size]`` at offset ``v % page_size``; -1 table
# entries read as empty (pos = -1), so unallocated tail pages cost nothing
# but the gather. SSM/conv state is O(1) per slot and stays slot-dense.
#
# All shapes are static: ``tables`` is a (slots, pages_per_slot) int32
# ARGUMENT of the compiled program, so growing/freeing/stealing pages never
# recompiles — exactly how the launcher keeps one executable per wave
# shape. Gather/scatter are plain XLA gathers (a Pallas paged-attention
# kernel that skips the materialized dense view is the TPU follow-on).

def paged_cache_init(cfg: ModelConfig, slots: int, n_pages: int,
                     page_size: int) -> list:
    """Pool caches: attention leaves paged over ``n_pages`` x ``page_size``
    (windowed layers use full pages too — windows are enforced by the pos
    mask, not by ring truncation); SSM state stays per-slot dense."""
    caches = []
    for g in cfg.groups:
        per_block = {}
        for i, b in enumerate(g.pattern):
            c: dict = {}
            if b.attn is not None:
                spec = (b.attn if b.attn.window is None
                        else dataclasses.replace(b.attn, window=None))
                c["attn"] = {
                    k: jnp.swapaxes(a, 1, 2) if k in HEAD_MAJOR else a
                    for k, a in attn_cache_init(n_pages, page_size,
                                                spec).items()}
            if b.ssm is not None:
                c["ssm"] = ssm_cache_init(slots, cfg.d_model, b.ssm)
            per_block[str(i)] = c
        caches.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (g.repeats,) + a.shape).copy()
            if g.repeats > 1 else a[None], per_block))
    return caches


def pool_page_size(pool: list) -> Optional[int]:
    """Page size of a paged cache pytree (None when the model has no
    attention caches to page — pure-SSM state is slot-dense)."""
    for gtree in pool:
        for btree in gtree.values():
            sub = btree.get("attn")
            if sub:
                return sub["pos"].shape[-1]
    return None


def _rows_at(leaf: jax.Array, idx: jax.Array) -> jax.Array:
    """leaf: (R, B, C, ...), idx: (B, W) -> rows (R, B, W, ...)."""
    return jax.vmap(lambda lf, ii: jnp.take(lf, ii, axis=1),
                    in_axes=(1, 0), out_axes=1)(leaf, idx)


def paged_gather(pool: list, tables: jax.Array) -> list:
    """Materialize the dense per-slot view of a paged pool.

    tables: (B, pages_per_slot) int32 page ids, -1 = unallocated. Returns
    caches shaped exactly like ``cache_init(cfg, B, vcap)`` output with
    ``vcap = pages_per_slot * page_size`` — ``decode_step`` runs on it
    unchanged, which is what makes the paged engine bit-compatible with
    the fixed-partition one."""
    clamped = jnp.maximum(tables, 0)
    B, n_per = tables.shape

    def attn_fn(sub):
        ps = sub["pos"].shape[-1]
        valid = jnp.repeat(tables >= 0, ps, axis=1)            # (B, vcap)
        out = {}
        for k, leaf in sub.items():
            g = jnp.take(leaf, clamped, axis=1)    # (R, B, n_per, [K,] ps, …)
            if k in HEAD_MAJOR:
                g = jnp.swapaxes(g, 3, 4)
            g = g.reshape(g.shape[0], B, n_per * ps, *g.shape[4:])
            if k == "pos":
                g = jnp.where(valid[None], g, -1)
            out[k] = g
        return out

    return _map_attn_subs(pool, attn_fn)


def paged_scatter(pool: list, dense: list, tables: jax.Array,
                  claim: jax.Array,
                  slot_ids: Optional[jax.Array] = None,
                  live: Optional[jax.Array] = None) -> list:
    """Commit dense cache rows holding absolute positions ``claim`` (B, W)
    back into the pool pages mapped by ``tables`` (B, pages_per_slot).

    A row is written only when the dense cache actually HOLDS its claimed
    position (``dense pos == claim`` — ring wrap and pad neutralization
    both make this false) and the target page is allocated; everything
    else lands on an out-of-range page index and is dropped by the
    scatter. SSM state is slot-dense, not paged: it is written at
    ``slot_ids`` (B,) rows of the pool's slot axis (out-of-range ids drop,
    which is how dummy batch-pad rows are discarded), or replaces the pool
    state wholesale when ``slot_ids`` is None (the decode path, where the
    dense batch IS the slot axis) — gated per slot by ``live`` (B,) bool:
    a stalled slot keeps its OLD state, so its retried step is truly
    identical (the recurrence must not absorb the same token twice)."""

    def attn_fn(pool_sub, dense_sub):
        ps = pool_sub["pos"].shape[-1]
        n_pages = pool_sub["pos"].shape[1]
        vcap = tables.shape[1] * ps
        v = jnp.where(claim >= 0, claim % vcap, 0)
        page = jnp.take_along_axis(tables, v // ps, axis=1)       # (B, W)
        off = v % ps
        cap_leaf = dense_sub["pos"].shape[2]
        j = jnp.where(claim >= 0, claim % cap_leaf, 0)
        held = _rows_at(dense_sub["pos"], j)[0]                   # (B, W)
        ok = (claim >= 0) & (held == claim) & (page >= 0)
        tgt = jnp.where(ok, page, n_pages)                        # OOB drops
        out = {}
        for k, pl in pool_sub.items():
            rows = _rows_at(dense_sub[k], j)                  # (R, B, W, …)
            if k in HEAD_MAJOR:
                # non-adjacent index arrays put their (B, W) dims first
                out[k] = pl.at[:, tgt, :, off].set(
                    jnp.moveaxis(rows, 0, 2).astype(pl.dtype), mode="drop")
            else:
                out[k] = pl.at[:, tgt, off].set(rows.astype(pl.dtype),
                                                mode="drop")
        return out

    def ssm_fn(pool_sub, dense_sub):
        if slot_ids is None:
            if live is None:
                return dense_sub
            return {k: jnp.where(
                live.reshape((1, -1) + (1,) * (pool_sub[k].ndim - 2)),
                dense_sub[k].astype(pool_sub[k].dtype), pool_sub[k])
                for k in pool_sub}
        return {k: pool_sub[k].at[:, slot_ids].set(
            dense_sub[k].astype(pool_sub[k].dtype), mode="drop")
            for k in pool_sub}

    return _zip_attn_subs(pool, dense, attn_fn, ssm_fn)


def paged_clear(pool: list, page_ids) -> list:
    """Mark the given pages empty (pos = -1) so a later owner never sees a
    previous request's keys. Called by the engine when pages are freed;
    k/v payloads are left in place — pos = -1 masks them everywhere."""
    ids = jnp.asarray(page_ids, jnp.int32)

    def attn_fn(sub):
        sub = dict(sub)
        sub["pos"] = sub["pos"].at[:, ids].set(-1, mode="drop")
        return sub

    return _map_attn_subs(pool, attn_fn)


def paged_copy(pool: list, src, dst) -> list:
    """Copy page payloads ``src`` -> ``dst`` on every attention leaf (the
    copy-on-write break: a shared page is duplicated into a private page
    before its first divergent write). src/dst: int32 page ids, scalar or
    (n,); out-of-range dst drops (used to no-op padded id lists)."""
    s = jnp.asarray(src, jnp.int32)
    d = jnp.asarray(dst, jnp.int32)

    def attn_fn(sub):
        return {k: leaf.at[:, d].set(jnp.take(leaf, s, axis=1), mode="drop")
                for k, leaf in sub.items()}

    return _map_attn_subs(pool, attn_fn)


def _paged_view(pool: list, tables: jax.Array, cfg: ModelConfig,
                fresh_ssm: Optional[int] = None) -> list:
    """Cache pytree for the leaf-level paged path: every attention leaf
    carries the pool pages plus ``table`` (broadcast over scan repeats so
    it rides the ``lax.scan`` xs axis); SSM leaves pass through slot-dense
    (decode) or are replaced with fresh zero state for a ``fresh_ssm``-row
    prefill batch (scattered to slots by the caller afterwards)."""
    out = []
    for g, gtree in zip(cfg.groups, pool):
        ng = {}
        for bi, btree in gtree.items():
            nb = {}
            for kind, sub in btree.items():
                if kind == "attn":
                    sub = dict(sub)
                    R = sub["pos"].shape[0]
                    sub["table"] = jnp.broadcast_to(
                        tables[None], (R,) + tables.shape)
                    nb[kind] = sub
                elif fresh_ssm is not None:
                    init = ssm_cache_init(fresh_ssm, cfg.d_model,
                                          g.pattern[int(bi)].ssm)
                    R = next(iter(sub.values())).shape[0]
                    nb[kind] = jax.tree_util.tree_map(
                        lambda a: jnp.broadcast_to(a[None], (R,) + a.shape),
                        init)
                else:
                    nb[kind] = sub
            ng[bi] = nb
        out.append(ng)
    return out


def _paged_unview(caches: list) -> list:
    """Strip the ``table`` entries a ``_paged_view`` forward echoes back."""
    def attn_fn(sub):
        return {k: v for k, v in sub.items() if k != "table"}
    return _map_attn_subs(caches, attn_fn)


def paged_prefill(params: dict, pool: list, tables: jax.Array,
                  tokens: jax.Array, lengths: jax.Array,
                  slot_ids: jax.Array, cfg: ModelConfig,
                  enc_out: Optional[jax.Array] = None, *,
                  starts: Optional[jax.Array] = None,
                  kernel: str = "gather"):
    """Batched multi-slot prefill straight into the page pool.

    tokens: (B, S) right-padded prompts; lengths: (B,) real lengths;
    tables: (B, pages_per_slot) page tables of the destination slots;
    slot_ids: (B,) destination slots for the SSM state (out-of-range =
    dummy row, dropped). Returns (last-real-token logits (B,1,V), pool).

    ``kernel`` selects the attention data path: "gather" (cold prompts)
    keeps the dense-materialize path (``prefill_batched`` + whole-tree
    ``paged_scatter`` — the bitwise-stable baseline); "pallas" — or any
    call with ``starts`` — runs the leaf-level paged path: fresh rows are
    scattered page-by-page inside each layer and queries attend the pool
    THROUGH the page table, so row b may continue from absolute position
    ``starts[b]`` with its earlier pages (e.g. a shared prefix) already
    resident. tokens then holds only the suffix and lengths its length."""
    if kernel == "gather" and starts is None:
        ps = pool_page_size(pool)
        vcap = tables.shape[1] * ps if ps else None
        logits, dense = prefill_batched(params, {"tokens": tokens}, cfg,
                                        lengths, enc_out=enc_out,
                                        capacity=vcap)
        S = tokens.shape[1]
        claim = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 tokens.shape)
        return logits, paged_scatter(pool, dense, tables, claim,
                                     slot_ids=slot_ids)

    B, S = tokens.shape
    st = (jnp.zeros((B,), jnp.int32) if starts is None
          else starts.astype(jnp.int32))
    positions = st[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    positions = jnp.where(
        jnp.arange(S, dtype=jnp.int32)[None] < lengths.astype(jnp.int32)[:, None],
        positions, -1)                                 # pad rows never write
    view = _paged_view(pool, tables, cfg.replace(paged_kernel=kernel),
                       fresh_ssm=B)
    h, new_caches, _ = lm_hidden(params, {"tokens": tokens,
                                          "positions": positions},
                                 cfg.replace(paged_kernel=kernel),
                                 caches=view, enc_out=enc_out)
    idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, S - 1)
    last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
    logits = lm_logits(params, last, cfg)

    def ssm_fn(new_sub, old_sub):
        return {k: old_sub[k].at[:, slot_ids].set(
            new_sub[k].astype(old_sub[k].dtype), mode="drop")
            for k in old_sub}

    return logits, _zip_attn_subs(_paged_unview(new_caches), pool,
                                  lambda n, o: n, ssm_fn)


def paged_decode_step(params: dict, pool: list, tables: jax.Array,
                      tokens: jax.Array, pos: jax.Array, cfg: ModelConfig,
                      enc_out: Optional[jax.Array] = None,
                      live: Optional[jax.Array] = None, *,
                      kernel: str = "gather"):
    """One batched decode step over the paged pool. tokens/pos: (B, 1).

    kernel="gather": materialize each slot's dense view (``paged_gather``),
    run the ordinary ``decode_step``, scatter the one new row per slot back
    to its page — the bitwise-stable baseline. kernel="pallas": no dense
    view is ever built — each attention leaf scatters its one fresh row
    into the pool and the Pallas kernel walks the page table in-kernel
    (``kernels.paged_attention``).

    ``live`` (B,) bool marks slots whose state may advance; a stalled
    (page-less) slot's attention write already drops on the missing page,
    and ``live=False`` drops its SSM-state write too, so the step can be
    retried bit-identically once a page frees."""
    if kernel == "gather":
        dense = paged_gather(pool, tables)
        logits, new_dense = decode_step(params, dense, tokens, pos, cfg,
                                        enc_out=enc_out)
        return logits, paged_scatter(pool, new_dense, tables, pos, live=live)

    view = _paged_view(pool, tables, cfg)
    h, new_caches, _ = lm_hidden(params, {"tokens": tokens,
                                          "positions": pos},
                                 cfg.replace(paged_kernel=kernel),
                                 caches=view, enc_out=enc_out)
    logits = lm_logits(params, h, cfg)

    def ssm_fn(new_sub, old_sub):
        if live is None:
            return new_sub
        return {k: jnp.where(
            live.reshape((1, -1) + (1,) * (old_sub[k].ndim - 2)),
            new_sub[k].astype(old_sub[k].dtype), old_sub[k])
            for k in old_sub}

    return logits, _zip_attn_subs(_paged_unview(new_caches), pool,
                                  lambda n, o: n, ssm_fn)


# ---------------------------------------------------------------------------
# Parameter counting (via eval_shape on init — no allocation, no formulas)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda: lm_init(jax.random.PRNGKey(0), cfg))
    total = 0

    def add(path, leaf):
        nonlocal total
        n = 1
        for s in leaf.shape:
            n *= s
        if active_only:
            names = [str(getattr(p, "key", "")) for p in path]
            if any(nm.startswith("we_") for nm in names):
                for g in cfg.groups:
                    for b in g.pattern:
                        if b.moe is not None:
                            n = int(n * b.moe.top_k / b.moe.n_experts)
                            break
        total += n

    jax.tree_util.tree_map_with_path(add, shapes)
    return total
