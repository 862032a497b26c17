"""Plain reference of qwen3-14b (Qwen3ForCausalLM) at the configuration's
widths and depth, and the seeded weights both it and the served program
read.

Forward, per the published architecture: token embedding; per layer
``h += o_proj(attn(rope(q_norm(q)), rope(k_norm(k)), v))`` on
``input_layernorm(h)`` with 40 query and 8 key/value heads of 128 (GQA,
causal, per-head RMSNorm on q and k before the rotary embedding), then
``h += down(silu(gate(x)) * up(x))`` on ``post_attention_layernorm(h)``;
a final RMSNorm and an untied ``lm_head``. Float32 throughout, every
matrix product at the highest precision, one sequence and one layer at a
time so that it fits beside the served weights. Nothing of the program is
imported.

One departure, a layout and not a change of model: the published rotary
embedding rotates the two halves of each head (i, i + 64); the weights
here are stored for rotation of adjacent pairs (2i, 2i + 1), the layout
the served program uses. The two are the same model under a fixed
permutation of each head's 128 query and key columns.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

_CACHE: dict = {}


def _key(seed: int):
    import jax
    word = np.random.SeedSequence([seed, 2]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make_weights(seed: int, hf: dict):
    """Seeded bfloat16 weights, made on the device in one jitted call, in
    the parameter layout of the served program (one scan group, layer
    axis first). Matrices are normal with std 1/sqrt(fan_in) (embedding
    and lm_head 0.02); norm scales are 1 + 0.1 N(0, 1), so the reference's
    reading of every norm is exercised."""
    import jax
    import jax.numpy as jnp
    d, f = hf["hidden_size"], hf["intermediate_size"]
    H, K, Dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    L, V = hf["num_hidden_layers"], hf["vocab_size"]
    bf = jnp.bfloat16

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, std):
            return (jax.random.normal(next(ks), shape, bf) * std).astype(bf)

        def scale(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape, bf)
                    ).astype(bf)

        layer = {
            "norm_attn": {"scale": scale((L, d))},
            "attn": {"wq": mat((L, d, H, Dh), d ** -0.5),
                     "wk": mat((L, d, K, Dh), d ** -0.5),
                     "wv": mat((L, d, K, Dh), d ** -0.5),
                     "wo": mat((L, H, Dh, d), (H * Dh) ** -0.5),
                     "q_norm": scale((L, Dh)), "k_norm": scale((L, Dh))},
            "norm_mlp": {"scale": scale((L, d))},
            "mlp": {"w_up": mat((L, d, f), d ** -0.5),
                    "w_down": mat((L, f, d), f ** -0.5),
                    "w_gate": mat((L, d, f), d ** -0.5)},
        }
        return {"embed": {"embedding": mat((V, d), 0.02)},
                "final_norm": {"scale": scale((d,))},
                "groups": [{"stacked": {"0": layer}, "shared": {}}],
                "lm_head": {"lm_head": mat((d, V), 0.02)}}

    return jax.block_until_ready(jax.jit(build)(_key(seed)))


# ----------------------------------------------------------------------
# reference forward
# ----------------------------------------------------------------------

def _q8(a):
    """Round to float8_e4m3fn, scaled per tensor into its range."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, policy: str):
    import jax
    import jax.numpy as jnp
    if policy == "float8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, pos, theta):
    """Rotate adjacent pairs (2i, 2i+1) of each head by pos * theta^(-2i/D)."""
    import jax.numpy as jnp
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None, None] * inv        # (T, 1, D/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _layer(h, w, hf: dict, policy: str):
    """One decoder layer over one sequence h (T, d), float32."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    H, K, Dh = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(T)
    a = _rms(h, w["norm_attn"], eps)
    q = _mm("td,dhk->thk", a, w["wq"], policy)
    k = _mm("td,dhk->thk", a, w["wk"], policy)
    v = _mm("td,dhk->thk", a, w["wv"], policy)
    q = _rope(_rms(q, w["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, w["k_norm"], eps), pos, theta)
    qg = q.reshape(T, K, H // K, Dh)
    s = jnp.einsum("tkgd,skd->kgts", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(Dh)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(T, H, Dh)
    h = h + _mm("thk,hkd->td", o, w["wo"], policy)
    m = _rms(h, w["norm_mlp"], eps)
    g = _mm("td,df->tf", m, w["w_gate"], policy)
    u = _mm("td,df->tf", m, w["w_up"], policy)
    return h + _mm("tf,fd->td", jax.nn.silu(g) * u, w["w_down"], policy)


def _jit(name: str, fn, static=()):
    import jax
    if name not in _CACHE:
        _CACHE[name] = jax.jit(fn, static_argnums=static)
    return _CACHE[name]


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return b


def logits_rows(params, hf: dict, seqs: List[Tuple[np.ndarray, np.ndarray]],
                policy: str = "float32"):
    """Reference logits (float32, on the device) at the rows that produced
    each sequence's served tokens: for ``(prompt, out)``, the last prompt
    position and every position fed an output token. Sequences are padded
    at the end to a power of two (causality keeps padding out of every
    real row)."""
    import jax
    import jax.numpy as jnp
    hfs = tuple(sorted((k, v) for k, v in hf.items()
                       if isinstance(v, (int, float))))
    hfd = dict(hfs)
    layer_fn = _jit("layer", lambda h, w, hfs, pol: _layer(
        h, w, dict(hfs), pol), static=(2, 3))
    stack = params["groups"][0]["stacked"]["0"]
    take = _jit("take", lambda t, i: jax.tree_util.tree_map(
        lambda a: a[i].astype(jnp.float32), t))
    emb = params["embed"]["embedding"]
    hs, rows = [], []
    for prompt, out in seqs:
        toks = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        T = _bucket(len(toks))
        pad = np.zeros(T, np.int32)
        pad[:len(toks)] = toks
        hs.append(jnp.take(emb, jnp.asarray(pad), axis=0).astype(jnp.float32))
        rows.append(np.arange(len(prompt) - 1, len(toks)))
    for i in range(hfd["num_hidden_layers"]):
        w = take(stack, i)
        w = {"norm_attn": w["norm_attn"]["scale"], **w["attn"],
             "norm_mlp": w["norm_mlp"]["scale"], **w["mlp"]}
        hs = [layer_fn(h, w, hfs, policy) for h in hs]
        del w
    head = params["lm_head"]["lm_head"]
    fin = params["final_norm"]["scale"].astype(jnp.float32)
    V = head.shape[1]
    parts = 8 if V % 8 == 0 else 1           # a float32 slice at a time
    proj = _jit("proj", lambda x, hd, pol: _mm(
        "td,dv->tv", x, hd.astype(jnp.float32), pol), static=(2,))
    out = []
    for h, r in zip(hs, rows):
        n = len(r)                           # pad rows: few program shapes
        idx = np.minimum(r[0] + np.arange(_bucket(n)), h.shape[0] - 1)
        x = _rms(h[jnp.asarray(idx)], fin, hfd["rms_norm_eps"])
        out.append(jnp.concatenate(
            [proj(x, head[:, i * V // parts:(i + 1) * V // parts], policy)
             for i in range(parts)], axis=1)[:n])
    return out


def _gap(ref, tok):
    """How far the reference logit of ``tok`` lies below the reference's
    best at each row, in units of the row's standard deviation (so the
    number reads alike at any width or vocabulary)."""
    import jax.numpy as jnp
    got = jnp.take_along_axis(ref, tok[:, None], axis=1)[:, 0]
    return np.asarray((ref.max(-1) - got) / ref.std(-1))


def served_gaps(params, hf: dict, seqs) -> np.ndarray:
    """For every served token, its gap below the reference's best (0
    where it is the best)."""
    import jax.numpy as jnp
    gaps = [_gap(lg, jnp.asarray(np.asarray(out, np.int32)))
            for lg, (_, out) in zip(logits_rows(params, hf, seqs), seqs)]
    return np.concatenate(gaps) if gaps else np.zeros(0)


def control_gaps(params, hf: dict, seqs) -> np.ndarray:
    """The control: at each of the same rows, the reference's gap of the
    token that the float8 computation of the same reference puts first."""
    import jax.numpy as jnp
    ref = logits_rows(params, hf, seqs, "float32")
    low = logits_rows(params, hf, seqs, "float8")
    gaps = [_gap(r, jnp.argmax(c, -1)) for r, c in zip(ref, low)]
    return np.concatenate(gaps) if gaps else np.zeros(0)
