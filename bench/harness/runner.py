"""One run of one cell: run the cell's system code, read its metrics, build
the result line."""
from __future__ import annotations

import contextlib
import glob
import math
import shutil
import tempfile
import time
from typing import Optional

from harness import device, manifest, trace as tracing


class Context:
    """What a cell's system code gets from the harness: the chips, the seed, the
    window length, host annotations, and the marks that start and end the
    window (set-up ends at the first, the trace covers the window).
    ``control`` puts the configuration's reference, one precision step
    below the configuration's, in the program's place for the check."""

    def __init__(self, cell, devs, peaks: dict, seed: int, seconds: float,
                 trace: bool, t_start: float, control: bool = False):
        self.cell, self.devs, self.peaks = cell, devs, peaks
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control = control
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.reduced: Optional[tracing.Reduced] = None
        self.memory_peak_bytes: Optional[int] = None
        self._trace_dir: Optional[str] = None

    def annotate(self, name: str):
        """A host span on the profiler's clock (cheap when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(tracing.HOST_PREFIX + name)

    def begin_window(self) -> float:
        """Set-up ends here: start the trace (traced runs) and the clock."""
        if self.trace:
            import jax
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=opts)
        t = time.perf_counter()
        self.setup_s = t - self.t_start
        return t

    def end_window(self) -> None:
        """Stop and reduce the trace (traced runs)."""
        if not self.trace or self._trace_dir is None:
            return
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(f"{self._trace_dir}/plugins/profile/*/"
                              f"*.xplane.pb")
            events = []
            for p in paths:
                events += tracing.load_xspace(p)
            host = [e for e in events
                    if e["name"].startswith(tracing.HOST_PREFIX)]
            if host:
                t0 = min(e["start"] for e in host)
                t1 = max(e["start"] + e["dur"] for e in host)
                self.reduced = tracing.reduce(events, t0, t1)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    def read_memory_peak(self) -> int:
        self.memory_peak_bytes = device.memory_peak_bytes(self.devs)
        return self.memory_peak_bytes


class Untimed:
    """The context system code gets outside a benchmark run (the knee
    sweep): no trace, no set-up clock."""

    trace = False
    peaks = None

    def annotate(self, name: str):
        return contextlib.nullcontext()

    def begin_window(self) -> float:
        return time.perf_counter()

    def end_window(self) -> None:
        pass


def run_cell(cell, devs, peaks: dict, seed: int, seconds: float,
             trace: bool, t_start: float, control: bool = False) -> dict:
    drv = manifest.load_module(manifest.system_path(cell.root, cell.system))
    ctx = Context(cell, devs, peaks, seed, seconds, trace, t_start, control)
    out = drv.run(cell, ctx)
    if ctx.memory_peak_bytes is None:
        ctx.read_memory_peak()
    obs = dict(out["obs"])
    obs.update(setup_s=ctx.setup_s, trace=ctx.reduced, peaks=peaks,
               chips=len(devs), seconds=seconds)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = manifest.load_module(manifest.reader_path(cell.root,
                                                           m["name"]))
        v = reader.read(obs)
        if v is None:
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = {k: {"value": float(v), "limit": float(lim)}
              for k, (v, lim) in out["checks"].items()}
    correct = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = int(ctx.memory_peak_bytes)
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": dev}
    if trace and ctx.reduced is not None:
        dev["busy_s"] = ctx.reduced.busy_s
        dev["window_s"] = ctx.reduced.window_s
        result["breakdown"] = ctx.reduced.breakdown()
    result["checks"] = checks
    return result
