"""Idle time named by the program's spans (``harness.spans``): the
program's tracer lands its spans in a real profiler trace, the
attribution of idle time on hand-made nested spans and on the recorded
v5e excerpt, and the span metrics on CPU-size runs of both cells."""
import glob
import json
import math
import os
import shutil
import tempfile
import time

import pytest

import small_cells
from harness import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_excerpt.json")


@pytest.mark.parametrize("enabled", [True, False])
def test_program_span_lands_in_the_profiler_trace(enabled):
    import jax
    import jax.numpy as jnp
    from repro.obs.trace import TRACER
    x = jnp.ones((64, 64))
    d = tempfile.mkdtemp()
    if enabled:
        TRACER.enable()
    try:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.outer"):
            with TRACER.span("probe.step"):
                (x @ x).block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        outer = [e for e in trace.load_xspace(path)
                 if e["name"] == "bench.outer"]
        program = spans.load_program_spans(path)
    finally:
        TRACER.disable()
        TRACER.clear()
        shutil.rmtree(d, ignore_errors=True)
    assert len(outer) == 1
    if not enabled:
        assert program == []
        return
    (probe,) = program
    assert probe["name"] == "repro.probe.step"
    o = outer[0]
    assert o["start"] <= probe["start"]
    assert probe["start"] + probe["dur"] <= o["start"] + o["dur"]


def _dev(name, start, dur, line=trace.OPS_LINE):
    return {"plane": "/device:TPU:0", "line": line, "name": name,
            "start": start, "dur": dur}


def _host(name, start, end):
    return {"plane": "/host:CPU", "line": "python3", "name": name,
            "start": start, "dur": end - start}


def test_idle_time_goes_to_the_innermost_span():
    # busy 10..20 and 60..70 of a 0..100 window: gaps 0..10, 20..60,
    # 70..100 (80 ns idle)
    ev = [_dev("fusion.1", 10, 10), _dev("fusion.2", 60, 10),
          _host("bench.engine_step", 0, 90),
          _host("repro.serve.run", 4, 85),
          _host("repro.serve.decode", 20, 50),
          _host("repro.serve.prefill", 20, 26),    # opens with decode
          _host("repro.serve.emit", 50, 58)]
    a = spans.attribute(ev, 0, 100)
    assert a.devices == ["/device:TPU:0"]
    want = {"bench.engine_step": 4 + 5, "repro.serve.run": 6 + 2 + 15,
            "repro.serve.prefill": 6, "repro.serve.decode": 24,
            "repro.serve.emit": 8, trace.UNTRACED: 10}
    assert a.idle_by_span == {k: pytest.approx(v * 1e-9)
                              for k, v in want.items()}
    assert sum(a.idle_by_span.values()) == pytest.approx(80e-9)
    assert a.idle_gaps == [("repro.serve.run", pytest.approx(10e-9)),
                           ("repro.serve.decode", pytest.approx(40e-9)),
                           ("repro.serve.run", pytest.approx(30e-9))]
    assert a.span_n["repro.serve.decode"] == 1
    assert a.span_s["repro.serve.run"] == pytest.approx(81e-9)
    # the metric readers on the same attribution
    obs = {"spans": a, "window": {"steps": 2}}
    assert spans.host_idle_ms(obs) == pytest.approx(1e3 * 61e-9 / 2)
    assert spans.engine_step_named_share(a) == pytest.approx(61 / 70)


def test_attribution_agrees_with_the_reduction_on_the_excerpt():
    with open(DATA) as f:
        events = json.load(f)["events"]
    t0, t1 = 48_878_218, 56_424_507
    r = trace.reduce(events, t0, t1)
    a = spans.attribute(events, t0, t1)
    assert sum(a.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert sorted(g for _, g in a.idle_gaps) == pytest.approx(
        sorted(g for _, g in r.idle_gaps))
    assert set(a.idle_by_span) <= {n for n in a.span_s} | {trace.UNTRACED}


@pytest.mark.parametrize("name,profile,readable", [
    ("launch-16k-repeat", True, ("launch.poll_wait_s", "launch.harvest_s")),
    ("qwen3-14b-chat", True, ("serve.queue_wait_p95_s",)),
    # the profiler off: no trace to attribute, the end-to-end metrics read
    ("launch-16k-repeat", False, ()),
])
def test_span_metrics_read_a_traced_run(name, profile, readable):
    import jax
    c = small_cells.cell(name)
    r = spans.run_cell(c, jax.devices()[:1], small_cells.PEAKS,
                       seed=2**33 + 17, seconds=2.0,
                       t_start=time.perf_counter(), profile=profile)
    assert r["correct"], r["checks"]
    assert {m["name"] for m in c.end_to_end} <= set(r["metrics"])
    assert ("spans" in r) == profile
    got = r["span_metrics"]
    # the CPU trace has no device plane: no device idle to name
    assert set(got) == set(readable)
    assert all(math.isfinite(got[k]["value"]) and got[k]["value"] >= 0
               for k in readable)
    if name == "qwen3-14b-chat":
        assert got["serve.queue_wait_p95_s"]["value"] <= \
            r["metrics"]["ttft_p95_s"]["value"]
    # without the program's spans and stamps each reads nothing
    bare = {"spans": None, "launches": [{"t_s": 1.0}], "window":
            {"steps": 3}, "seconds": 2.0, "drain_s": 1.0,
            "requests": [{"ok": True, "ttft_s": 0.1}]}
    assert all(fn(bare) is None for fn, _ in spans.METRICS.values())
