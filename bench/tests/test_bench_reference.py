"""The plain float32 reference of qwen3-14b-stage against the served
program at the configuration's smoke size: prefill, then decode through
``PagedServeEngine``, agree with the reference's full forward on every
logit row."""
import numpy as np
import pytest

import small_cells

# bfloat16 weights and activations against float32 over 2 layers: each
# product rounds to 2^-9 relative, so a row's logits move by a few
# hundredths of the row's spread; a wrong mechanism moves them by its
# whole spread
ROW_TOL = 0.1


@pytest.fixture(scope="module")
def served():
    import jax
    from repro.serve.engine import Request
    c = small_cells.cell("qwen3-14b-chat")
    drv = small_cells.manifest.load_module(
        small_cells.manifest.system_path(small_cells.ROOT, "serve"))
    ref = c.reference()
    hf = c.config
    cfg = drv.model_config(hf, hf["program"])
    params = ref.make_weights(7, hf)
    eng = drv.make_engine(cfg, params, hf["engine"])
    rows = {}
    eng.logit_sink = lambda r, row: rows.setdefault(r.rid, []).append(
        np.asarray(row, np.float32))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n), max_new=6)
            for i, n in enumerate((5, 17, 40))]
    eng.run(reqs)
    jax.block_until_ready(eng.kv)
    return ref, hf, params, reqs, rows


def test_prefill_and_decode_logits_match_the_reference(served):
    ref, hf, params, reqs, rows = served
    seqs = [(np.asarray(r.prompt), np.asarray(r.out)) for r in reqs]
    want = ref.logits_rows(params, hf, seqs)
    for r, w in zip(reqs, want):
        got = np.stack(rows[r.rid])
        w = np.asarray(w)
        assert got.shape == w.shape == (len(r.out), hf["vocab_size"])
        err = np.abs(got - w).max(-1) / w.std(-1)
        assert err.max() < ROW_TOL, (r.rid, err)


def test_served_tokens_sit_at_the_reference_best(served):
    ref, hf, params, reqs, _ = served
    seqs = [(np.asarray(r.prompt), np.asarray(r.out)) for r in reqs]
    gaps = ref.served_gaps(params, hf, seqs)
    assert gaps.shape == (sum(len(r.out) for r in reqs),)
    assert gaps.max() < ROW_TOL


def test_a_wrong_token_shows_as_a_gap(served):
    ref, hf, params, reqs, _ = served
    r = reqs[2]
    bad = np.asarray(r.out).copy()
    bad[3] = (bad[3] + 1) % hf["vocab_size"]
    gaps = ref.served_gaps(params, hf, [(np.asarray(r.prompt), bad)])
    assert gaps[3] > 1.0
