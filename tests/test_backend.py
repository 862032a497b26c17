"""LaunchBackend protocol contract: dispatch/poll/result lifecycle, output
equivalence across serial/array/pipelined, pipelining depth, donation
gating, and the launcher<->serve shared compile cache."""
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.backend import (ArrayBackend, LaunchBackend,
                                PipelinedBackend, SerialBackend, WaveHandle,
                                make_backend)
from repro.core.compile_cache import CompileCache
from repro.core.llmr import LLMapReduce


def app(x):
    return (x * 3.0).sum(axis=-1)


@pytest.fixture()
def cache(tmp_path):
    return CompileCache(cache_dir=str(tmp_path / "aot"))


def _backends(cache):
    from repro.dist.backend import DistributedBackend
    return [SerialBackend(), ArrayBackend(cache=cache),
            PipelinedBackend(cache=cache),
            ArrayBackend(cache=cache, inner_lanes=4),
            PipelinedBackend(cache=cache, inner_lanes=4, depth=3),
            # the multi-host fabric speaks the same protocol end-to-end
            # over BOTH wires — queue pairs and per-node TCP connections
            # (generous lease: a busy CI box must not false-kill nodes)
            DistributedBackend(n_nodes=2, cache=cache,
                               heartbeat_timeout_s=30.0),
            DistributedBackend(n_nodes=2, cache=cache, transport="socket",
                               heartbeat_timeout_s=30.0)]


def _close_all(backends):
    for be in backends:                 # dist backends own node threads
        if hasattr(be, "close"):
            be.close()


def test_all_backends_satisfy_protocol(cache):
    backends = _backends(cache)
    try:
        for be in backends:
            assert isinstance(be, LaunchBackend)
            assert isinstance(be.name, str) and be.max_in_flight >= 1
    finally:
        _close_all(backends)


def test_factory_rejects_unknown_kind():
    with pytest.raises(ValueError):
        make_backend("slurm")


@given(n=st.integers(1, 48))
@settings(max_examples=10, deadline=None)
def test_backend_outputs_identical(n):
    """The tentpole contract: every backend computes the same launch.
    Each example gets a fresh cache dir of its own (a function-scoped
    fixture would be shared across hypothesis examples)."""
    inputs = np.random.default_rng(n).standard_normal((n, 8)).astype(
        np.float32)
    expect = inputs.sum(-1) * 3.0
    with tempfile.TemporaryDirectory(prefix="repro-aot-") as d:
        backends = _backends(CompileCache(cache_dir=d))
        try:
            for be in backends:
                out, rec = be.launch(app, inputs, n)
                got = (np.asarray([np.asarray(o) for o in out])
                       if isinstance(out, list) else np.asarray(out))
                np.testing.assert_allclose(got, expect, rtol=1e-5,
                                           atol=1e-4, err_msg=be.name)
                assert rec.n_instances == n
                assert rec.t_first_result > 0.0
        finally:
            _close_all(backends)


def test_wavehandle_lifecycle(cache):
    be = PipelinedBackend(cache=cache)
    inputs = np.ones((8, 4), np.float32)
    h = be.dispatch(app, inputs, 8)
    assert isinstance(h, WaveHandle)
    out, rec = h.result()
    assert h.poll()                       # after result, always ready
    np.testing.assert_allclose(np.asarray(out), np.full(8, 12.0))
    # idempotent: second result() returns the same harvest
    out2, rec2 = h.result()
    assert rec2 is rec and out2 is out


def test_pipelined_keeps_waves_in_flight(cache):
    """With depth=2 the driver must not barrier every wave: dispatch of
    wave k+1 happens before wave k is harvested."""
    events = []

    class Probe(PipelinedBackend):
        def dispatch(self, fn, chunk, n):
            events.append("dispatch")
            h = super().dispatch(fn, chunk, n)
            orig = h.result
            h.poll = lambda: False      # deterministic: only the depth
                                        # barrier may force a harvest

            def result():
                events.append("harvest")
                return orig()
            h.result = result
            return h

    inputs = np.ones((64, 4), np.float32)
    llmr = LLMapReduce(wave_size=8, backend=Probe(cache=cache))
    out, report = llmr.map_reduce(app, inputs)
    assert report.waves == 8
    np.testing.assert_allclose(np.asarray(out), np.full(64, 12.0))
    # a fully-synchronous driver alternates strictly; the pipelined driver
    # must somewhere run two dispatches with no harvest between them
    joined = ",".join(events)
    assert "dispatch,dispatch" in joined


def test_donation_disabled_on_cpu(cache):
    be = PipelinedBackend(cache=cache, donate=True)
    assert be.donate is False        # CPU backends cannot donate buffers


def test_inner_lanes_fall_back_when_indivisible(cache):
    """Regression: the fallback used to be silent — the user's fan-out
    config was dropped with no signal. It must now land in the record's
    extra and warn once (and only once) per backend."""
    import warnings
    be = ArrayBackend(cache=cache, inner_lanes=5)
    inputs = np.ones((12, 4), np.float32)      # 12 % 5 != 0 -> flat vmap
    with pytest.warns(RuntimeWarning, match="inner_lanes=5"):
        out, rec = be.launch(app, inputs, 12)
    assert rec.fanout == {"sched": 1, "node": 12, "core": 1}
    assert rec.extra["inner_lanes_fallback"] == {
        "requested": 5, "wave": 12, "used": (12, 1)}
    np.testing.assert_allclose(np.asarray(out), np.full(12, 12.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error")         # second launch: no warning
        _, rec2 = be.launch(app, inputs, 12)
    assert rec2.extra["inner_lanes_fallback"]["requested"] == 5


def test_dispatch_accepts_per_wave_inner_lanes_override(cache):
    """The autoscaling controller re-plans the node/core fan-out per
    wave through dispatch(..., inner_lanes=...)."""
    be = ArrayBackend(cache=cache)
    out, rec = be.dispatch(app, np.ones((16, 4), np.float32), 16,
                           inner_lanes=4).result()
    assert rec.fanout == {"sched": 1, "node": 4, "core": 4}
    np.testing.assert_allclose(np.asarray(out), np.full(16, 12.0))


def test_serial_attributes_per_task_submit_to_t_schedule():
    """Regression: SerialBackend never set t_schedule, so the serial
    baseline's per-task scheduler cost — exactly the cost the paper's
    array launch eliminates — showed as 0.0 in the fig6 CSV and in
    levels()['sched']."""
    be = SerialBackend()
    inputs = np.ones((6, 4), np.float32)
    _, rec = be.launch(app, inputs, 6)
    assert rec.t_schedule > 0.0
    assert rec.levels()["sched"] == rec.t_schedule
    # sched + node + core partition the measured wall clock: nothing of
    # the per-task submit cost hides inside t_spawn any more
    assert rec.total == pytest.approx(
        rec.t_schedule + rec.t_stage + rec.t_spawn)
    assert rec.t_first_result > 0.0
    assert rec.t_first_result <= rec.t_spawn + 1e-9
    # per-instance trace+compile dwarfs the actual execution — the
    # whole point of the serial-VM baseline
    assert rec.t_schedule > rec.t_spawn


def test_serial_overhead_counts_as_scheduler_cost():
    be = SerialBackend(per_task_overhead_s=0.01)
    _, rec = be.launch(app, np.ones((3, 4), np.float32), 3)
    assert rec.t_schedule >= 3 * 0.01


def test_serve_and_launch_share_compile_cache(cache):
    """An executable compiled by the serving engine must be a cache hit
    for a second engine over the same backend cache (and vice versa)."""
    import jax
    from repro.configs import get_config
    from repro.models.lm import lm_init
    from repro.serve.engine import Request, ServeEngine

    cfg = get_config("qwen3-14b", smoke=True)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=8),
                    max_new=4) for i in range(2)]

    eng1 = ServeEngine(cfg, params, slots=2, capacity=64,
                       backend=ArrayBackend(cache=cache))
    eng1.run(list(reqs), max_steps=50)
    assert eng1.stats["compile_sources"]["step"] == "compiled"

    for r in reqs:
        r.out, r.done = [], False
    eng2 = ServeEngine(cfg, params, slots=2, capacity=64,
                       backend=ArrayBackend(cache=cache))
    stats = eng2.run(list(reqs), max_steps=50)
    assert stats["compile_sources"]["step"] == "memory"
    assert all(v in ("memory", "disk")
               for v in stats["compile_sources"].values())
    assert all(r.done for r in reqs)


def test_launch_record_row_includes_t_first_result(cache):
    from repro.core.telemetry import HEADER
    be = ArrayBackend(cache=cache)
    _, rec = be.launch(app, np.ones((4, 4), np.float32), 4)
    assert "t_first_result" in HEADER
    row = rec.row()
    assert len(row.split(",")) == len(HEADER.split(","))
    assert float(row.split(",")[5]) == pytest.approx(rec.t_first_result,
                                                     abs=1e-4)
