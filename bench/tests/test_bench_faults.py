"""A whole run of each cell, at CPU size and with the harness's look for a
chip skipped, comes out correct; with the timed path broken underneath,
in each way the cell can break, it comes out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

import small_cells


@pytest.fixture
def launch_cell():
    return small_cells.cell("launch-16k-repeat")


@pytest.fixture
def serve_cell():
    return small_cells.cell("qwen3-14b-chat")


def _patch_dispatch(monkeypatch, alter):
    from repro.core.backend import ArrayBackend
    orig = ArrayBackend.dispatch

    def dispatch(self, fn, chunk, n, **kw):
        h = orig(self, fn, chunk, n, **kw)
        h.out = alter(h.out, chunk, n)
        return h

    monkeypatch.setattr(ArrayBackend, "dispatch", dispatch)


def test_launch_sound_run_is_correct(launch_cell):
    r = small_cells.run(launch_cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "launch_s", "first_result_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged"])
def test_launch_fault_is_not_correct(launch_cell, monkeypatch, fault):
    alter = {
        # one instance's answer changed where the wave produces it
        "answer_altered": lambda out, chunk, n: out.at[n // 2, 0].add(0.25),
        # the second half of every wave never computed
        "half_left_out": lambda out, chunk, n: out.at[n // 2:].set(0),
        # every instance hands back its input unchanged
        "state_unchanged": lambda out, chunk, n: jnp.asarray(chunk),
    }[fault]
    _patch_dispatch(monkeypatch, alter)
    # the cell's own seeded sample of each launch is what reads the fault
    r = small_cells.run(launch_cell)
    assert not r["correct"], r["checks"]


def test_serve_sound_run_is_correct(serve_cell):
    r = small_cells.run(serve_cell, seconds=2.0)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["metrics"]) == {"setup_s", "ttft_p95_s", "tpot_p95_s"}


@pytest.mark.parametrize("fault", ["token_altered", "half_left_out",
                                   "state_unchanged"])
def test_serve_fault_is_not_correct(serve_cell, monkeypatch, fault):
    from repro.serve.engine import PagedServeEngine
    orig = PagedServeEngine._step_executable

    def step(self):
        kv = self.kv
        nxt, logits = orig(self)
        nxt = np.array(nxt)
        if fault == "token_altered":
            # one slot's token changed where the step produces it, the
            # slot taking turns step by step
            b = self.stats["steps"] % self.slots
            nxt[b] = (nxt[b] + 1) % self.cfg.vocab
        elif fault == "half_left_out":
            # the second half of the slots take the first half's tokens
            half = self.slots // 2
            nxt[half:] = nxt[:self.slots - half]
        else:
            # the step hands back its KV state unchanged
            self.kv = kv
        self.tokens = jnp.asarray(nxt, jnp.int32)[:, None]
        return nxt, logits

    monkeypatch.setattr(PagedServeEngine, "_step_executable", step)
    # every slot busy; the cell's own seeded sample of finished requests
    # is what reads the fault
    serve_cell.traffic = dict(serve_cell.traffic, rate_per_s=30.0)
    r = small_cells.run(serve_cell, seconds=2.0)
    assert not r["correct"], r["checks"]
