"""Work counts against counts made by hand at a small shape."""
import pytest

import small_cells  # noqa: F401
from harness import work


M = work.DenseLM(d_model=8, n_heads=2, n_kv=1, head_dim=4, d_ff=16,
                 vocab=10, layers=2)


def test_model_flops_per_token():
    # q 8x2x4 + k, v 8x1x4 each + o 2x4x8 + gate, up, down 8x16 each
    assert M.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128
    # a decoded token with 5 keys: 2 x 576 x 2 layers + 4 x 2 x 4 x 5 x 2
    # + logits 2 x 8 x 10
    assert M.token_flops(5, logits=True) == 2304 + 320 + 160
    assert M.token_flops(5, logits=False) == 2304 + 320
    assert M.decode_flops([5, 2]) == 2784 + 2304 + 128 + 160
    # a 3-token prompt: 3 tokens of matrix products, causal pairs
    # 1 + 2 + 3 = 6, logits once
    assert M.prefill_flops(3) == 6912 + 4 * 2 * 4 * 6 * 2 + 160
    # after 4 resident tokens: pairs 5 + 6 + 7 = 18
    assert M.prefill_flops(3, start=4) == 6912 + 4 * 2 * 4 * 18 * 2 + 160


def test_paged_attention_counts_live_rows():
    # decode: slots with 5 and 2 live rows, one query each
    f, b = work.paged_attention([1, 1], [5, 2], n_heads=2, n_kv=1,
                                head_dim=4)
    assert f == 4 * 2 * 4 * 7
    # each live row: k and v (1 head x 4 x 2 bytes each) + a 4-byte
    # position; each query row: q in and out (2 heads x 4 x 2 bytes each)
    assert b == 7 * (16 + 4) + 2 * 32
    # prefill of 3 tokens: 6 causal pairs
    f, b = work.paged_attention([3], [3], 2, 1, 4, causal_pairs=[6])
    assert (f, b) == (192, 3 * 20 + 3 * 32)


def test_roofline_names_the_bound():
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert work.roofline_s(224, 204, peaks) == (pytest.approx(2.04e-9),
                                                "memory")
    assert work.roofline_s(1e6, 10, peaks) == (pytest.approx(1e-6),
                                               "compute")


def test_qwen3_stage_layer_is_330m_matmul_parameters():
    m = work.DenseLM(5120, 40, 8, 128, 17408, 151936, 10)
    assert m.layer_matmul_params == 330_301_440
