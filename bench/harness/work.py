"""Work counts kept with the benchmark: operations and bytes an algorithm
needs, from shapes and live lengths alone.

They are the same whichever implementation runs: paged attention counts
the KV rows that are live (keys a query may attend), never the pages a
kernel walks or a dense view a gather builds, so every path is held to
one yardstick.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple


@dataclass(frozen=True)
class DenseLM:
    """A decoder of ``layers`` GQA + gated-MLP blocks (the widths of the
    configuration file)."""
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int

    @property
    def layer_matmul_params(self) -> int:
        d, H, K, Dh, f = (self.d_model, self.n_heads, self.n_kv,
                          self.head_dim, self.d_ff)
        return d * H * Dh + 2 * d * K * Dh + H * Dh * d + 3 * d * f

    def attention_flops(self, context: int) -> int:
        """QK^T and PV of one query over ``context`` keys, all layers."""
        return 4 * self.n_heads * self.head_dim * context * self.layers

    def token_flops(self, context: int, logits: bool) -> int:
        """Model operations of one token that attends ``context`` keys
        (itself included); ``logits`` adds the vocabulary projection,
        which a prefill computes for its last position only."""
        f = 2 * self.layer_matmul_params * self.layers
        f += self.attention_flops(context)
        if logits:
            f += 2 * self.d_model * self.vocab
        return f

    def prefill_flops(self, length: int, start: int = 0) -> int:
        """A prompt of ``length`` new tokens after ``start`` resident ones:
        causal, so token i attends start + i + 1 keys; logits once."""
        n = length
        f = 2 * self.layer_matmul_params * self.layers * n
        ctx = n * start + n * (n + 1) // 2
        f += 4 * self.n_heads * self.head_dim * ctx * self.layers
        return f + 2 * self.d_model * self.vocab

    def decode_flops(self, contexts: Iterable[int]) -> int:
        """One decode step of the live slots, slot b attending
        ``contexts[b]`` keys."""
        return sum(self.token_flops(c, logits=True) for c in contexts)


def paged_attention(q_rows: Sequence[int], kv_rows: Sequence[int],
                    n_heads: int, n_kv: int, head_dim: int,
                    kv_bytes: int = 2, q_bytes: int = 2,
                    causal_pairs: Sequence[int] = ()) -> Tuple[int, int]:
    """(flops, bytes) of one paged-attention call, one layer.

    Slot b has ``q_rows[b]`` query positions and ``kv_rows[b]`` live KV
    rows. Operations: QK^T and PV over the (query, key) pairs that the
    mask keeps — ``causal_pairs[b]`` when given (prefill), else every
    query against every live row (decode). Bytes: each live K and V row of
    every kv head read once, its position once, queries read and outputs
    written once."""
    pairs = (list(causal_pairs) if causal_pairs
             else [q * k for q, k in zip(q_rows, kv_rows)])
    flops = 4 * n_heads * head_dim * sum(pairs)
    kv = sum(kv_rows) * (2 * n_kv * head_dim * kv_bytes + 4)
    qo = sum(q_rows) * 2 * n_heads * head_dim * q_bytes
    return flops, kv + qo


def roofline_s(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")

