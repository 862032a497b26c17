"""Idle device time named by the program's own spans.

The program's tracer (``repro.obs.trace``) mirrors every span it records
into the profiler trace as a host annotation named ``repro.<span>``, on
the clock of the device ops. This module reads those annotations beside
the harness's own (``bench.``) and names idle time by them:

* ``load_program_spans(path)``: the ``repro.`` host events of an
  ``.xplane.pb`` (``trace.load_xspace`` keeps only the ``bench.`` ones);
* ``attribute(events, t0, t1)`` over the window ``[t0, t1]`` (ns):

  * ``idle_by_span``: each instant of each idle gap goes to the
    innermost host span covering it (the one that started last),
    ``host:untraced`` where none does; seconds per span name, averaged
    over the devices, so the values sum to the window's idle seconds;
  * ``span_s`` / ``span_n``: seconds in the window and count of each
    host span name;
  * ``idle_gaps``: each gap named by the span that owns most of it.

``SpanContext`` is the harness's run context with the program's tracer
on (tracing only, no metrics registry) for the window, and ``run_cell``
runs a cell under it, with or without the profiler (``bench/spans.py``
is the command line). The window's trace is reduced after the run, not
at the window's end, so requests still in flight at the end do not wait
on the reduction. ``METRICS`` read what such a run observed; each reads
None where the program emitted no such span or stamp. None of this is
part of a benchmark run: ``bench/run.py`` reduces its trace with
``trace.reduce`` alone and never turns the program's tracer on.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import heapq
import math
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from harness import device, manifest, runner, stats, trace

PROGRAM_PREFIX = "repro."
HOST_PREFIXES = (trace.HOST_PREFIX, PROGRAM_PREFIX)


def load_program_spans(path: str) -> List[dict]:
    """The program's host spans in an ``.xplane.pb`` as event rows."""
    from jax.profiler import ProfileData
    rows: List[dict] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": e.name, "start": int(e.start_ns),
                                 "dur": int(e.duration_ns)})
    return rows


@dataclass
class Attribution:
    devices: List[str]
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    span_n: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self, top: int = 10) -> dict:
        return {"idle_by_span": dict(sorted(self.idle_by_span.items(),
                                            key=lambda kv: -kv[1])),
                "idle_gaps": [list(g) for g in
                              sorted(self.idle_gaps,
                                     key=lambda g: -g[1])[:top]],
                "span_s": self.span_s, "span_n": self.span_n}


def _owners(spans: List[Tuple[int, int, str]], t0: int,
            t1: int) -> List[Tuple[int, int, str]]:
    """Contiguous segments covering ``[t0, t1]``, each with the innermost
    span covering it: of the spans open there, the one that started last
    (the shorter of two that started together)."""
    bounds = sorted({t0, t1, *(s for s, _, _ in spans),
                     *(e for _, e, _ in spans)})
    by_start = sorted(spans)
    heap: list = []
    out: List[Tuple[int, int, str]] = []
    i = 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][0] <= a:
            s, e, name = by_start[i]
            heapq.heappush(heap, (-s, e - s, e, name))
            i += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        name = heap[0][3] if heap else trace.UNTRACED
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _split(segments: List[Tuple[int, int, str]], starts: List[int],
           s: int, e: int) -> Dict[str, int]:
    """Nanoseconds of ``[s, e]`` per owner in ``segments``."""
    own: Dict[str, int] = defaultdict(int)
    k = max(bisect.bisect_right(starts, s) - 1, 0)
    while k < len(segments) and segments[k][0] < e:
        ss, se, name = segments[k]
        ov = min(se, e) - max(ss, s)
        if ov > 0:
            own[name] += ov
        k += 1
    return own


def attribute(events: List[dict], t0: int, t1: int) -> Attribution:
    """Idle time and host spans over the window ``[t0, t1]`` (ns); see the
    module doc. Busy time is found as ``trace.reduce`` finds it."""
    ops: Dict[str, list] = defaultdict(list)
    mods: Dict[str, list] = defaultdict(list)
    host: List[Tuple[int, int, str]] = []
    for ev in events:
        s = max(ev["start"], t0)
        e = min(ev["start"] + ev["dur"], t1)
        if e < s:
            continue
        if trace._is_device_plane(ev["plane"]):
            (ops if ev["line"] == trace.OPS_LINE
             else mods)[ev["plane"]].append((s, e))
        elif ev["name"].startswith(HOST_PREFIXES):
            host.append((s, e, ev["name"]))
    devices = sorted(set(ops) | set(mods))
    out = Attribution(devices=devices)
    span_s: Dict[str, float] = defaultdict(float)
    span_n: Dict[str, int] = defaultdict(int)
    for s, e, name in host:
        span_s[name] += (e - s) / 1e9
        span_n[name] += 1
    out.span_s, out.span_n = dict(span_s), dict(span_n)
    segments = _owners(host, t0, t1)
    starts = [seg[0] for seg in segments]
    idle: Dict[str, float] = defaultdict(float)
    for dev in devices:
        busy = trace._union(ops.get(dev) or mods.get(dev, []))
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for k in range(0, len(edges), 2):
            gs, ge = edges[k], edges[k + 1]
            if ge <= gs:
                continue
            own = _split(segments, starts, gs, ge)
            for name, ns in own.items():
                idle[name] += ns / 1e9 / len(devices)
            out.idle_gaps.append((max(own, key=own.get), (ge - gs) / 1e9))
    out.idle_by_span = dict(idle)
    return out


class SpanContext(runner.Context):
    """The harness's run context with the program's tracer on, tracing
    only, for the window. With the profiler on (``trace``), ``reduce``
    reduces the window's trace as a benchmark run does (``reduced``) and
    also attributes it to the program's spans (``spans``)."""

    spans: Optional[Attribution] = None

    def begin_window(self) -> float:
        from repro.obs.trace import TRACER
        TRACER.enable()
        return super().begin_window()

    def end_window(self) -> None:
        from repro.obs.trace import TRACER
        TRACER.disable()
        TRACER.clear()
        if self.trace and self._trace_dir is not None:
            import jax
            jax.profiler.stop_trace()

    def reduce(self) -> None:
        """Read and reduce the window's trace (after the run)."""
        if self._trace_dir is None:
            return
        try:
            events: List[dict] = []
            program: List[dict] = []
            for p in glob.glob(f"{self._trace_dir}/plugins/profile/*/"
                               f"*.xplane.pb"):
                events += trace.load_xspace(p)
                program += load_program_spans(p)
            host = [e for e in events
                    if e["name"].startswith(trace.HOST_PREFIX)]
            if host:
                t0 = min(e["start"] for e in host)
                t1 = max(e["start"] + e["dur"] for e in host)
                self.reduced = trace.reduce(events, t0, t1)
                self.spans = attribute(events + program, t0, t1)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


@contextlib.contextmanager
def _queue_wait(drv):
    """Serving systems: each request record also gets ``queue_s``, its
    admission stamp less its due time (None where the program stamps no
    admission)."""
    records = getattr(drv, "records", None)
    if records is None:
        yield
        return

    def with_queue(reqs, due):
        out = records(reqs, due)
        for rec, r, d in zip(out, reqs, due):
            t = getattr(r, "t_admit", None)
            rec["queue_s"] = (t - d) if rec["ok"] and t is not None \
                else None
        return out

    drv.records = with_queue
    try:
        yield
    finally:
        drv.records = records


# -- metrics of a run under SpanContext: read(obs) -> value or None -------

def queue_wait_p95_s(obs) -> Optional[float]:
    """p95 (nearest rank, as ``ttft_p95_s``) over the window's requests of
    due time to admission; a failed request reads as a miss."""
    reqs = obs.get("requests")
    if not reqs or any(r["ok"] and r.get("queue_s") is None for r in reqs):
        return None
    miss = obs["seconds"] + obs["drain_s"]
    return stats.nearest_rank([r["queue_s"] if r["ok"] else miss
                               for r in reqs], 0.95)


def _serve_idle_s(sp: Attribution) -> float:
    return sum(v for k, v in sp.idle_by_span.items()
               if k.startswith(PROGRAM_PREFIX + "serve."))


def host_idle_ms(obs) -> Optional[float]:
    """Device-idle milliseconds inside the engine's spans per engine step
    of the window."""
    sp, w = obs.get("spans"), obs.get("window")
    if sp is None or not sp.devices or not w or not w["steps"] \
            or not any(k.startswith(PROGRAM_PREFIX + "serve.")
                       for k in sp.span_n):
        return None
    return 1e3 * _serve_idle_s(sp) / w["steps"]


def _per_launch(obs, names) -> Optional[float]:
    sp, launches = obs.get("spans"), obs.get("launches")
    if sp is None or not launches \
            or PROGRAM_PREFIX + "llmr.map_reduce" not in sp.span_n:
        return None
    return sum(sp.span_s.get(PROGRAM_PREFIX + n, 0.0)
               for n in names) / len(launches)


def poll_wait_s(obs) -> Optional[float]:
    """Seconds per launch the launch loop spent pausing between polls."""
    return _per_launch(obs, ("llmr.poll_wait",))


def harvest_s(obs) -> Optional[float]:
    """Seconds per launch in harvesting waves and assembling the result."""
    return _per_launch(obs, ("harvest", "llmr.assemble"))


METRICS = {"serve.queue_wait_p95_s": (queue_wait_p95_s, "s"),
           "serve.host_idle_ms": (host_idle_ms, "ms"),
           "launch.poll_wait_s": (poll_wait_s, "s"),
           "launch.harvest_s": (harvest_s, "s")}


def engine_step_named_share(sp: Attribution) -> Optional[float]:
    """Share of the idle time inside the harness's engine steps that an
    engine span names."""
    named = _serve_idle_s(sp)
    whole = named + sp.idle_by_span.get(trace.HOST_PREFIX + "engine_step",
                                        0.0)
    return named / whole if whole > 0 else None


def run_cell(cell, devs, peaks: dict, seed: int, seconds: float,
             t_start: float, profile: bool = True) -> dict:
    """A run of ``cell`` with the program's tracer on, traced by the
    profiler when ``profile``: the result line of a benchmark run, its
    end-to-end metrics included, and ``spans``: the metrics above and
    the attribution."""
    drv = manifest.load_module(manifest.system_path(cell.root, cell.system))
    ctx = SpanContext(cell, devs, peaks, seed, seconds, profile, t_start)
    with _queue_wait(drv):
        out = drv.run(cell, ctx)
    ctx.reduce()
    if ctx.memory_peak_bytes is None:
        ctx.read_memory_peak()
    obs = dict(out["obs"], setup_s=ctx.setup_s, trace=ctx.reduced,
               peaks=peaks, chips=len(devs), seconds=seconds,
               spans=ctx.spans)
    metrics = {}
    for m in cell.end_to_end + cell.per_layer:
        reader = manifest.load_module(manifest.reader_path(cell.root,
                                                           m["name"]))
        v = reader.read(obs)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    span_metrics = {}
    for name, (fn, unit) in METRICS.items():
        v = fn(obs)
        if v is not None:
            span_metrics[name] = {"value": float(v), "unit": unit}
    checks = {k: {"value": float(v), "limit": float(lim)}
              for k, (v, lim) in out["checks"].items()}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = int(ctx.memory_peak_bytes)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "span_metrics": span_metrics, "device": dev}
    if ctx.reduced is not None:
        dev["busy_s"] = ctx.reduced.busy_s
        dev["window_s"] = ctx.reduced.window_s
        result["breakdown"] = ctx.reduced.breakdown()
    if ctx.spans is not None:
        result["spans"] = ctx.spans.breakdown()
        result["spans"]["engine_step_named_share"] = \
            engine_step_named_share(ctx.spans)
    result["checks"] = checks
    return result
