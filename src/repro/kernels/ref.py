"""Pure-jnp oracles for every kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal=True, window=None, softcap=None,
                  scale=None):
    """q: (B,H,Sq,D), k/v: (B,K,Sk,D/Dv). Naive materialized attention."""
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1)[None, None, :, None], p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def paged_attention_ref(q, k, v, kpos, tables, q_pos, *, q2=None, k2=None,
                        scale=None, causal=True, window=None, softcap=None):
    """Dense oracle for ``paged_attention``: materialize each slot's page
    list into a per-slot view (exactly ``models.lm.paged_gather`` for one
    leaf), then run naive masked attention.

    q: (B,S,H,Dk), k/v: (P,K,ps,Dk/Dv) head-major pool pages, kpos:
    (P,ps), tables: (B,npps),
    q_pos: (B,S). Optional q2/k2 add a second score component (MLA
    absorbed form). Returns (B,S,H,Dv) in v.dtype.
    """
    B, S, H, Dk = q.shape
    P, K, ps, _ = k.shape
    npps = tables.shape[1]
    vcap = npps * ps
    if scale is None:
        scale = 1.0 / math.sqrt(Dk + (q2.shape[-1] if q2 is not None else 0))

    cl = jnp.maximum(tables, 0)

    def dense(leaf):                        # (P,K,ps,D) -> (B,vcap,K,D)
        d = jnp.take(leaf, cl, axis=0)                  # (B,npps,K,ps,D)
        return d.transpose(0, 1, 3, 2, 4).reshape(B, vcap, K, -1)

    kd, vd = dense(k), dense(v)
    kp = jnp.take(kpos, cl, axis=0).reshape(B, vcap)
    kp = jnp.where(jnp.repeat(tables >= 0, ps, axis=1), kp, -1)

    if K != H:
        kd = jnp.repeat(kd, H // K, axis=2)
        vd = jnp.repeat(vd, H // K, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   kd.astype(jnp.float32))
    if q2 is not None:
        k2d = dense(k2)
        if K != H:
            k2d = jnp.repeat(k2d, H // K, axis=2)
        s += jnp.einsum("bqhd,bshd->bhqs", q2.astype(jnp.float32),
                        k2d.astype(jnp.float32))
    s = s * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = (kp >= 0)[:, None, :]                              # (B,1,S)
    if causal:
        mask = mask & (kp[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & ((q_pos[:, :, None] - kp[:, None, :]) < window)
    mask = mask[:, None]                                      # (B,1,Q,S)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqs,bshd->bqhd", p,
                      vd.astype(jnp.float32)).astype(v.dtype)


def ssd_ref(x, dt, A, B, C):
    """Sequential SSM recurrence (the semantic ground truth for SSD).

    x: (Bz,S,H,P), dt: (Bz,S,H), A: (H,), B/C: (Bz,S,N).
    Returns y: (Bz,S,H,P), final state (Bz,H,P,N).
    """
    Bz, S, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, Bt, Ct = inp                     # (Bz,H,P),(Bz,H),(Bz,N),(Bz,N)
        decay = jnp.exp(dtt * A)                  # (Bz,H)
        h = h * decay[..., None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtt, xt, Bt)
        y = jnp.einsum("bn,bhpn->bhp", Ct, h)
        return h, y

    h0 = jnp.zeros((Bz, H, P, N), jnp.float32)
    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          B.transpose(1, 0, 2).astype(jnp.float32),
          C.transpose(1, 0, 2).astype(jnp.float32))
    h_f, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), h_f
