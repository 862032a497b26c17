"""Unified observability: fabric-wide tracing, a process-local metrics
registry, and the live health plane built over them.

Recording pillars, both designed to be nearly free when disabled:

- ``repro.obs.trace``: lightweight spans in a bounded ring buffer. Trace
  context rides inside the wire frames themselves (SUBMIT/STAGE carry the
  parent span id; RESULT carries the node-side spans back), so one wave
  renders as a single span tree from ``llmr.map_reduce`` down to worker
  exec. Export as Chrome-trace JSON (open in Perfetto) or a text flame
  summary.
- ``repro.obs.metrics``: counters / gauges / fixed-bucket histograms with
  cheap hot-path increments and snapshot/delta reads. Node-side registries
  fly home piggybacked on HEARTBEAT frames.

The live plane reads what the pillars record:

- ``repro.obs.timeseries``: bounded ring time-series (downsample on
  overflow, O(1) append) plus the background ``Sampler`` that derives
  counter rates / gauge values / histogram window means continuously.
- ``repro.obs.health``: per-node median/MAD anomaly scoring over shard
  walls and heartbeat gaps -> ``healthy``/``degraded``/``outlier``
  verdicts with hysteresis (surfaced in ``NodeRegistry`` rollups and
  ``MapReduceReport.health``).
- ``repro.obs.flight``: the flight recorder — atomic JSON postmortem
  bundles on node death / wave failure / SLO breach / explicit trigger
  (``python -m repro.obs.flight dump``).
- ``repro.obs.statusd``: opt-in stdlib HTTP status endpoint
  (``/healthz`` ``/fleet`` ``/slo`` ``/series`` + one HTML fleet page).

Enable the pillars with :func:`enable_observability` (pass
``sampling=True`` to also start the background sampler);
``python -m repro.obs.report trace.json`` renders a captured trace and
``--metrics`` renders a metrics snapshot.

Program spans beside the device ops: turn the tracer on around a
profiler capture,

    enable_observability(tracing=True, metrics=False)
    with jax.profiler.trace(log_dir):
        ...                       # launches, engine steps
    disable_observability()

and every span also lands in the profiler trace as ``repro.<span name>``
on the host lane of the thread that ran it, on the clock of the device
ops, so an idle stretch of the device shows the span it fell in. The
launch path: ``llmr.map_reduce`` over ``llmr.load`` (the wave loader),
``dispatch`` (``backend.compile_lookup``, ``backend.enqueue``),
``llmr.poll_wait`` (each pause between readiness polls), ``harvest``
(``backend.result``) and ``llmr.assemble`` (wave concat and reduce). The
serve engine: ``serve.run`` over ``serve.admit`` (``serve.prefill``),
``serve.pre_step``, ``serve.decode`` and ``serve.emit``. Each request's
wait for a slot is ``RequestRecord.queue_s`` (enqueue to admission,
before its prefill dispatch).
"""
from typing import Optional

from .health import HealthScorer
from .metrics import REGISTRY, MetricsRegistry, counter, gauge, histogram
from .timeseries import RingSeries, Sampler
from .trace import TRACER, Tracer, new_span_id

__all__ = [
    "REGISTRY", "MetricsRegistry", "counter", "gauge", "histogram",
    "TRACER", "Tracer", "new_span_id",
    "RingSeries", "Sampler", "HealthScorer",
    "enable_observability", "disable_observability", "sampler",
]

#: the process-global background sampler (created on first use; running
#: only between enable_observability(sampling=True) and
#: disable_observability())
_SAMPLER: Optional[Sampler] = None


def sampler() -> Optional[Sampler]:
    """The global background sampler, or None if never started."""
    return _SAMPLER


def enable_observability(tracing: bool = True, metrics: bool = True,
                         sampling: bool = False,
                         sample_interval_s: float = 0.5) -> None:
    """Turn on the global tracer and/or metrics registry for this
    process; ``sampling=True`` also starts the background time-series
    sampler (one snapshot read per ``sample_interval_s`` — off every
    hot path)."""
    global _SAMPLER
    if tracing:
        TRACER.enable()
    if metrics:
        REGISTRY.enable()
    if sampling:
        if _SAMPLER is None:
            _SAMPLER = Sampler(REGISTRY, interval_s=sample_interval_s)
        _SAMPLER.interval_s = max(0.05, sample_interval_s)
        _SAMPLER.start()


def disable_observability() -> None:
    """Turn both pillars off and stop the sampler (buffers are kept;
    use .clear() to drop them)."""
    TRACER.disable()
    REGISTRY.disable()
    if _SAMPLER is not None:
        _SAMPLER.stop()
