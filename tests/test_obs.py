"""Fabric-wide observability: the metrics registry (counters, gauges,
fixed-bucket histograms, node piggyback rollup), the tracer (ring,
parent/child linkage, Chrome-trace round trip), and the acceptance test
— one fleet wave whose EXPORTED span tree links scheduler dispatch ->
pump send -> node stage/exec -> harvest via the span ids that
propagated through the wire frames."""
import json
import time

import numpy as np
import pytest

from repro.core.compile_cache import CompileCache
from repro.core.llmr import LLMapReduce
from repro.dist import DistributedBackend
from repro.obs import (REGISTRY, TRACER, disable_observability,
                       enable_observability)
from repro.obs.metrics import MetricsRegistry, StatsDict
from repro.obs.trace import (chrome_trace, flame_summary, make_span,
                             span_tree, spans_from_chrome)


def app(x):
    return (x * 3.0).sum(axis=-1)


@pytest.fixture()
def obs():
    """Both pillars on, with a guaranteed clean slate before and after."""
    REGISTRY.clear()
    TRACER.clear()
    enable_observability()
    yield
    disable_observability()
    REGISTRY.clear()
    TRACER.clear()


# ----------------------------------------------------------------------
# metrics registry
# ----------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("c") is c          # memoized by name

    g = reg.gauge("g")
    g.set(2.5)
    g.max(1.0)                            # max() never moves down
    assert g.value == 2.5
    g.max(7.0)
    assert g.value == 7.0

    h = reg.histogram("h", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 100.0):
        h.observe(v)
    assert h.counts == [1, 2, 1]          # <=0.1, <=1.0, +inf overflow
    assert h.count == 4
    assert h.mean() == pytest.approx((0.05 + 0.5 + 0.5 + 100.0) / 4)
    assert h.quantile(0.5) == 1.0         # bucket upper bound estimate
    assert h.quantile(1.0) == float("inf")


def test_snapshot_and_delta_attribute_one_window():
    reg = MetricsRegistry(enabled=True)
    reg.counter("frames").inc(10)
    reg.gauge("depth").set(3)
    reg.histogram("lat", bounds=(1.0,)).observe(0.5)
    prev = reg.snapshot()
    reg.counter("frames").inc(7)
    reg.gauge("depth").set(9)
    reg.histogram("lat", bounds=(1.0,)).observe(2.0)
    d = reg.delta(prev)
    assert d["frames"] == 7               # counters subtract
    assert d["depth"] == 9                # gauges report latest
    assert d["lat"]["count"] == 1         # histogram counts subtract
    assert d["lat"]["counts"] == [0, 1]
    # no prev -> the delta IS the snapshot
    assert reg.delta(None)["frames"] == 17


def test_clear_keeps_cached_instruments_attached():
    """Long-lived components (the frame pump, node loops) cache their
    instrument objects at construction. clear() must zero IN PLACE — a
    clear that replaced the objects would orphan those caches and every
    later increment would vanish from snapshots."""
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("pump.frames_out")
    h = reg.histogram("pump.drain_batch", bounds=(1.0,))
    g = reg.gauge("pump.outbuf_hwm")
    c.inc(3)
    h.observe(0.5)
    g.set(7)
    reg.clear()
    assert reg.snapshot()["pump.frames_out"] == 0
    c.inc(2)                              # the cached reference still counts
    h.observe(2.0)
    g.set(1)
    snap = reg.snapshot()
    assert snap["pump.frames_out"] == 2
    assert snap["pump.drain_batch"]["counts"] == [0, 1]
    assert snap["pump.outbuf_hwm"] == 1
    assert reg.counter("pump.frames_out") is c


def test_stats_dict_mirrors_increments_only_while_enabled(obs):
    s = StatsDict("t.cache", {"hits": 0, "misses": 0})
    s["hits"] += 3
    s["misses"] += 1
    assert s["hits"] == 3                 # the dict idiom still works
    assert REGISTRY.snapshot()["t.cache.hits"] == 3
    assert REGISTRY.snapshot()["t.cache.misses"] == 1
    REGISTRY.disable()
    s["hits"] += 5                        # not mirrored while disabled
    assert s["hits"] == 8
    assert REGISTRY.snapshot()["t.cache.hits"] == 3


def test_retire_node_preserves_dead_incarnation_totals():
    """A dead node's last cumulative snapshot folds into a baseline so
    the fleet rollup keeps its work; a NEW incarnation under the same id
    then adds on top instead of silently replacing (the rejoin
    double-count / undercount fix)."""
    reg = MetricsRegistry(enabled=True)
    reg.ingest_node("n0", {"node.shards": 4}, incarnation="a")
    reg.retire_node("n0")
    assert reg.nodes_rollup()["node.shards"] == 4   # dead totals survive
    reg.ingest_node("n0", {"node.shards": 2}, incarnation="b")
    assert reg.nodes_rollup()["node.shards"] == 6   # 4 dead + 2 new
    # retire is idempotent: a second call with no live snapshot is a no-op
    reg.retire_node("n0")
    reg.retire_node("n0")
    assert reg.nodes_rollup()["node.shards"] == 6


def test_zombie_same_incarnation_never_double_counts():
    """A node condemned by a lease blip whose worker loop never actually
    died keeps COUNTING CUMULATIVELY: when its beats resume with the
    same incarnation nonce, the baseline fold is undone — its totals
    must not be counted once in the baseline and again live."""
    reg = MetricsRegistry(enabled=True)
    reg.ingest_node("n0", {"node.shards": 4,
                           "node.exec_s": {"bounds": [1.0],
                                           "counts": [4, 0],
                                           "sum": 0.4, "count": 4}},
                    incarnation="a")
    reg.retire_node("n0")                   # suspected dead (lease blip)
    reg.ingest_node("n0", {"node.shards": 6,
                           "node.exec_s": {"bounds": [1.0],
                                           "counts": [6, 0],
                                           "sum": 0.6, "count": 6}},
                    incarnation="a")        # same loop, still counting
    roll = reg.nodes_rollup()
    assert roll["node.shards"] == 6         # not 4 + 6
    assert roll["node.exec_s"]["count"] == 6
    assert roll["node.exec_s"]["sum"] == pytest.approx(0.6)


def test_node_ingest_latest_wins_and_rollup_sums():
    reg = MetricsRegistry(enabled=True)
    # node snapshots are CUMULATIVE: a newer snapshot replaces, the
    # rollup then sums across nodes
    reg.ingest_node("n0", {"node.shards": 2,
                           "node.exec_s": {"bounds": [1.0],
                                           "counts": [2, 0],
                                           "sum": 0.4, "count": 2}})
    reg.ingest_node("n0", {"node.shards": 5,
                           "node.exec_s": {"bounds": [1.0],
                                           "counts": [5, 0],
                                           "sum": 1.0, "count": 5}})
    reg.ingest_node("n1", {"node.shards": 3,
                           "node.exec_s": {"bounds": [1.0],
                                           "counts": [2, 1],
                                           "sum": 3.0, "count": 3}})
    roll = reg.nodes_rollup()
    assert roll["node.shards"] == 8
    assert roll["node.exec_s"]["counts"] == [7, 1]
    assert roll["node.exec_s"]["count"] == 8
    assert roll["node.exec_s"]["sum"] == pytest.approx(4.0)


# ----------------------------------------------------------------------
# tracer
# ----------------------------------------------------------------------

def test_tracer_disabled_is_noop():
    TRACER.disable()
    assert TRACER.start("x") is None
    TRACER.finish(None)                   # safe on the disabled path
    assert TRACER.context() is None


def test_span_parenting_follows_the_thread_stack(obs):
    root = TRACER.start("root", where="driver", push=True)
    child = TRACER.start("child")         # inherits the pushed current
    TRACER.finish(child)
    TRACER.finish(root)
    spans = {s["name"]: s for s in TRACER.spans()}
    assert spans["child"]["parent_id"] == spans["root"]["span_id"]
    assert spans["child"]["trace_id"] == spans["root"]["trace_id"]
    assert TRACER.current() is None       # stack fully popped


def test_wire_context_tuple_reparents_across_threads(obs):
    """The (trace_id, span_id) tuple a frame carries is a full parent:
    a span started from it — or a raw make_span dict built node-side —
    lands in the same tree."""
    parent = TRACER.start("shard")
    tc = parent.context()
    remote = TRACER.start("pump.send", parent=tc, where="pump")
    TRACER.finish(remote)
    TRACER.ingest([make_span("node.exec", tc[0], tc[1], time.time(),
                             0.01, where="node:n0")])
    TRACER.finish(parent)
    spans = {s["name"]: s for s in TRACER.spans()}
    pid = spans["shard"]["span_id"]
    assert spans["pump.send"]["parent_id"] == pid
    assert spans["node.exec"]["parent_id"] == pid
    assert spans["node.exec"]["trace_id"] == spans["shard"]["trace_id"]


def test_chrome_trace_roundtrip_and_flame(tmp_path):
    t0 = time.time()
    spans = [
        make_span("root", "t1", None, t0, 1.0, where="driver",
                  span_id="s1"),
        make_span("leaf", "t1", "s1", t0 + 0.1, 0.4, where="pump",
                  span_id="s2", attrs={"bytes": 33}),
        make_span("leaf", "t1", "s1", t0 + 0.5, 0.2, where="pump",
                  span_id="s3"),
    ]
    doc = chrome_trace(spans)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert names == {"thread_name"}       # per-where lane labels
    back = spans_from_chrome(doc)
    assert {s["span_id"] for s in back} == {"s1", "s2", "s3"}
    by_id = {s["span_id"]: s for s in back}
    assert by_id["s2"]["parent_id"] == "s1"
    assert by_id["s2"]["attrs"]["bytes"] == 33
    assert by_id["s2"]["t0"] == pytest.approx(t0 + 0.1, abs=1e-3)
    roots, children = span_tree(back)
    assert [r["span_id"] for r in roots] == ["s1"]
    assert len(children["s1"]) == 2
    flame = flame_summary(back)
    assert "root" in flame and "x2" in flame   # same-name siblings merge

    # the CLI report renders the same file
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(doc))
    from repro.obs import report
    assert report.main([str(path)]) == 0
    assert report.main([str(path), "--trace-id", "missing"]) == 1


def test_ring_is_bounded(obs):
    TRACER.enable(capacity=8)
    try:
        for i in range(50):
            TRACER.finish(TRACER.start(f"s{i}"))
        spans = TRACER.spans()
        assert len(spans) == 8
        assert spans[-1]["name"] == "s49"  # newest kept, oldest dropped
    finally:
        TRACER.enable(capacity=16384)


def test_wrapped_ring_exports_no_orphan_parent_refs(obs):
    """Overflow the ring so parents are evicted while their children
    survive: the Chrome-trace export must not emit parent_id values
    that point outside the document — the survivors become roots."""
    TRACER.enable(capacity=8)
    try:
        root = TRACER.start("root", push=True)
        for i in range(20):                  # push root out of the ring
            TRACER.finish(TRACER.start(f"child{i}"))
        TRACER.finish(root)
        # drop the root span itself from the export set too
        spans = [s for s in TRACER.spans() if s["name"] != "root"]
        assert all(s.get("parent_id") for s in spans)  # links recorded...
        doc = chrome_trace(spans)
        ids = {e["args"]["span_id"] for e in doc["traceEvents"]
               if e["ph"] == "X"}
        for ev in doc["traceEvents"]:
            if ev["ph"] != "X":
                continue
            pid = ev["args"].get("parent_id")
            assert pid is None or pid in ids  # ...but never exported dangling
        # the round trip treats the de-parented survivors as roots
        roots, _ = span_tree(spans_from_chrome(doc))
        assert len(roots) == len(spans)
    finally:
        TRACER.enable(capacity=16384)


def test_report_renders_wrapped_ring_trace(obs, tmp_path):
    """report.main on a wrapped-ring export: orphaned children render as
    roots, no crash, exit 0."""
    TRACER.enable(capacity=4)
    try:
        root = TRACER.start("root", push=True)
        for i in range(12):
            TRACER.finish(TRACER.start(f"leaf{i}"))
        TRACER.finish(root)
        path = str(tmp_path / "wrapped.json")
        TRACER.export_json(path)
    finally:
        TRACER.enable(capacity=16384)
    from repro.obs import report
    assert report.main([path]) == 0


# ----------------------------------------------------------------------
# acceptance: one fleet wave, one exported tree, scheduler -> core
# ----------------------------------------------------------------------

def test_fleet_wave_exports_linked_span_tree(obs, tmp_path):
    cache = CompileCache(cache_dir=str(tmp_path / "aot"))
    be = DistributedBackend(n_nodes=2, cache=cache, heartbeat_s=0.02,
                            heartbeat_timeout_s=5.0)
    try:
        x = np.random.default_rng(0).standard_normal((48, 8)).astype(
            np.float32)
        llmr = LLMapReduce(wave_size=24, backend=be)
        out, rep = llmr.map_reduce(app, x)
        np.testing.assert_allclose(np.asarray(out), app(x), rtol=1e-5,
                                   atol=1e-4)

        # node-side registries fly home piggybacked on HEARTBEAT frames
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if REGISTRY.nodes_rollup().get("node.shards", 0) >= 4:
                break
            time.sleep(0.02)
        roll = REGISTRY.nodes_rollup()
        assert roll.get("node.shards", 0) >= 4    # 2 waves x 2 nodes
        assert roll["node.exec_s"]["count"] >= 4
    finally:
        be.close()

    # the report reads the same registry the benchmarks do
    assert rep.metrics.get("pump.frames_out", 0) > 0
    assert rep.metrics.get("pump.bytes_out", 0) > 0
    snap = REGISTRY.snapshot()
    assert snap.get("registry.renewals", 0) > 0

    path = str(tmp_path / "trace.json")
    TRACER.export_json(path)
    with open(path) as f:
        spans = spans_from_chrome(json.load(f))
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    roots = by_name["llmr.map_reduce"]
    assert len(roots) == 1
    root = roots[0]
    tid = root["trace_id"]
    assert all(s["trace_id"] == tid for s in spans
               if s["name"] in ("dispatch", "shard", "pump.send",
                                "node.stage", "node.exec", "harvest"))

    # scheduler dispatch under the root, one per wave
    dispatch_ids = {s["span_id"] for s in by_name["dispatch"]}
    assert len(dispatch_ids) == rep.waves == 2
    assert all(s["parent_id"] == root["span_id"]
               for s in by_name["dispatch"])
    # per-node shard spans under their wave's dispatch
    shard_ids = {s["span_id"] for s in by_name["shard"]}
    assert len(shard_ids) == 4                     # 2 waves x 2 nodes
    assert all(s["parent_id"] in dispatch_ids for s in by_name["shard"])
    # pump sends and node-side stage/exec parent to the PROPAGATED
    # shard span id — the link crossed the wire, not a thread stack
    assert len(by_name["pump.send"]) >= 4
    assert all(s["parent_id"] in shard_ids for s in by_name["pump.send"])
    assert len(by_name["node.exec"]) == 4
    assert all(s["parent_id"] in shard_ids for s in by_name["node.exec"])
    assert all(s["attrs"].get("n") for s in by_name["node.exec"])
    assert len(by_name["node.stage"]) >= 1
    assert all(s["parent_id"] in shard_ids for s in by_name["node.stage"])
    # harvest closes the loop under the root
    assert all(s["parent_id"] == root["span_id"]
               for s in by_name["harvest"])
    assert len(by_name["harvest"]) == 2

    # the flame summary renders the whole tree without error
    assert "llmr.map_reduce" in flame_summary(spans)


def test_rejoin_same_id_keeps_metrics_baseline(obs, tmp_path):
    """Kill a node and rejoin it under the SAME id: the fleet rollup
    must keep the dead incarnation's shard totals AND count the new
    incarnation's on top — neither the pre-fix latest-wins undercount
    nor a fold-twice double count."""
    cache = CompileCache(cache_dir=str(tmp_path / "aot"))
    be = DistributedBackend(n_nodes=2, cache=cache, heartbeat_s=0.02,
                            heartbeat_timeout_s=0.5)
    try:
        x = np.ones((16, 4), np.float32)
        be.launch(app, x, 16)
        # wait until BOTH nodes' snapshots flew home with their shard
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            snaps = REGISTRY.node_snapshots()
            if (snaps.get("node0", {}).get("node.shards", 0) >= 1
                    and snaps.get("node1", {}).get("node.shards", 0) >= 1):
                break
            time.sleep(0.02)
        before = REGISTRY.nodes_rollup().get("node.shards", 0)
        assert before >= 2

        be.agents["node1"].kill()           # hard death, lease expires
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if be.registry.state("node1") == "dead":
                break
            time.sleep(0.01)
        assert be.registry.state("node1") == "dead"
        # the dead incarnation's totals survived condemnation
        assert REGISTRY.nodes_rollup().get("node.shards", 0) == before

        # rejoin under the same id (a restarted worker on the same host)
        from repro.dist.node import NodeAgent
        fresh = NodeAgent("node1", be.registry, cache=cache,
                          transport=be.transport, heartbeat_s=0.02)
        be.add_node(fresh)
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if be.registry.state("node1") == "alive":
                break
            time.sleep(0.01)
        assert be.registry.state("node1") == "alive"

        be.launch(app, x, 16)
        want = before + 2                   # wave 2: one shard per node
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if REGISTRY.nodes_rollup().get("node.shards", 0) >= want:
                break
            time.sleep(0.02)
        assert REGISTRY.nodes_rollup().get("node.shards", 0) == want
    finally:
        be.close()


def test_observability_off_adds_no_spans_or_metrics(tmp_path):
    disable_observability()
    REGISTRY.clear()
    TRACER.clear()
    cache = CompileCache(cache_dir=str(tmp_path / "aot"))
    be = DistributedBackend(n_nodes=2, cache=cache, heartbeat_s=0.02,
                            heartbeat_timeout_s=5.0)
    try:
        x = np.ones((16, 4), np.float32)
        out, rep = be.launch(app, x, 16)
        np.testing.assert_allclose(np.asarray(out), app(x), rtol=1e-5)
    finally:
        be.close()
    assert TRACER.spans() == []
    assert REGISTRY.snapshot().get("pump.frames_out", 0) == 0
    assert "tc" not in rep.extra          # no trace context on the wire


def test_child_span_needs_a_current_span(obs):
    """``child`` nests under the thread's current span and records
    nothing on a thread that has none (a node worker running a shard)."""
    with TRACER.child("orphan"):
        pass
    with TRACER.span("root"):
        with TRACER.child("leaf") as leaf:
            assert TRACER.current() is leaf
    spans = {s["name"]: s for s in TRACER.spans()}
    assert set(spans) == {"root", "leaf"}
    assert spans["leaf"]["parent_id"] == spans["root"]["span_id"]
    assert TRACER.current() is None
