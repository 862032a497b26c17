"""The trace reduction and the peaks table, on a small trace recorded on a
TPU v5 lite (bench/tests/data/trace_v5e_excerpt.json: the first events of
a jitted step around the Pallas paged-attention kernel, and the harness's
host spans). Expected values are worked out by hand from the listed
events."""
import json
import os

import pytest

import small_cells  # noqa: F401  (puts bench/ on the path)
from harness import device, trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_excerpt.json")


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return json.load(f)["events"]


def test_busy_idle_and_kernel_time_over_one_step(events):
    # window: 47,662,700 .. 47,689,100 ns (the first step's ops)
    r = trace.reduce(events, 47_662_700, 47_689_100)
    assert r.devices == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(26_400e-9)
    # union of the 12 ops: 14 + 2 + 337 + 3 + 85 + 1 + 231 + 148 + 25,449
    # + 33 ns (touching intervals merge)
    assert r.busy_s == pytest.approx(26_303e-9)
    assert r.idle_share == pytest.approx(97 / 26_400)
    assert sum(g for _, g in r.idle_gaps) == pytest.approx(97e-9)
    assert max(g for _, g in r.idle_gaps) == pytest.approx(68e-9)
    assert {n for n, _ in r.idle_gaps} == {trace.UNTRACED}
    assert r.kernel_s("paged_attention") == pytest.approx(25_446e-9)
    assert r.kernel_s("paged_attention", module="jit_step_fn") == \
        pytest.approx(25_446e-9)
    assert r.kernel_s("paged_attention", module="jit_prefill_fn") == 0
    # the module is clipped to the window: 47,662,711 .. 47,689,100
    assert r.module_seconds("jit_step_fn") == (pytest.approx(26_389e-9), 1)
    assert r.op_s["jit_step_fn/paged_attention.1"] == \
        pytest.approx(25_446e-9)
    top = r.breakdown()["device_ops"][0]
    assert top[0] == "jit_step_fn/paged_attention.1"


def test_idle_gaps_are_named_by_the_host_span_that_overlaps_most(events):
    # window: the harness spans, 48,878,218 .. 56,424,507 ns; one step's
    # module (54,492,345 + 26,482) runs in it and no op of it is listed,
    # so the module itself is the busy interval
    r = trace.reduce(events, 48_878_218, 56_424_507)
    assert r.busy_s == pytest.approx(26_482e-9)
    gaps = sorted(r.idle_gaps, key=lambda g: -g[1])
    # 48,878,218 .. 54,492,345: bench.sleep overlaps 4,606,557 ns,
    # bench.call 999,459
    assert gaps[0] == ("bench.sleep", pytest.approx(5_614_127e-9))
    # 54,518,827 .. 56,424,507: bench.sleep 1,239,310, bench.call 660,850
    assert gaps[1] == ("bench.sleep", pytest.approx(1_905_680e-9))
    assert r.breakdown()["idle_gaps"][0][0] == "bench.sleep"


def test_op_names_lose_their_signature():
    assert trace.op_name("%fusion.3 = bf16[4,8]{1,0} fusion(%a), kind=kLoop") \
        == "fusion.3"
    assert trace.base_name("jit_step_fn(7179862671952560031)") == \
        "jit_step_fn"


def test_peaks_are_keyed_by_device_kind():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["source"] == "Google Cloud documentation, TPU v5e"
    with pytest.raises(device.UnknownDevice):
        device.peaks("TPU v99")


def test_a_loop_op_keeps_only_its_self_time():
    ev = [{"plane": "/device:TPU:0", "line": "XLA Modules",
           "name": "jit_step_fn(1)", "start": 0, "dur": 100},
          {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "while.4",
           "start": 10, "dur": 80},
          {"plane": "/device:TPU:0", "line": "XLA Ops",
           "name": "paged_attention.8", "start": 20, "dur": 30},
          {"plane": "/device:TPU:0", "line": "XLA Ops", "name": "fusion.1",
           "start": 50, "dur": 25}]
    r = trace.reduce(ev, 0, 100)
    assert r.op_s["jit_step_fn/while.4"] == pytest.approx(25e-9)
    assert r.op_s["jit_step_fn/paged_attention.8"] == pytest.approx(30e-9)
    assert r.kernel_s("paged_attention", module="jit_step_fn") == \
        pytest.approx(30e-9)
    assert r.busy_s == pytest.approx(80e-9)
