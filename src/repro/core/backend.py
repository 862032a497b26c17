"""LaunchBackend: one protocol for every way this repo starts instances.

The paper's launch tree is scheduler -> node -> core: ONE scheduler
interaction fans an array job out to nodes, each node fans out to cores,
and staging overlaps with dispatch so no level ever waits on a level it
does not depend on. This module is that tree for a JAX mesh:

  SerialBackend     the heavyweight-VM baseline — every instance pays its
                    own trace+compile+dispatch (Fig 6's serial curve).
  ArrayBackend      the LLMapReduce array job — ONE compiled program whose
                    task axis is vmapped and (optionally) sharded over the
                    mesh ``data`` axis; per-instance marginal cost is a
                    vmap lane. Compiles through the persistent
                    ``CompileCache`` so repeat launches skip compile even
                    across processes.
  PipelinedBackend  ArrayBackend + JAX async dispatch: wave k+1 is sliced,
                    staged, and enqueued while wave k is still executing
                    on device (double-buffered; ``donate_argnums`` on wave
                    buffers off-CPU), results harvested by non-blocking
                    readiness polling instead of a per-wave
                    ``block_until_ready`` barrier.

Hierarchy: a wave of W tasks optionally splits into (W // inner_lanes)
outer tasks x ``inner_lanes`` inner vmap lanes — the outer axis is the
"node" level (sharded over the mesh ``data`` axis when divisible), the
inner axis the "core" level. Per-level counts land in
``LaunchRecord.fanout`` and per-level timings in ``LaunchRecord.levels()``.

``dispatch()`` is the one verb: it returns a ``WaveHandle`` whose result
may still be computing. Synchronous backends advertise
``max_in_flight = 1`` (the policy layer harvests immediately);
``PipelinedBackend`` advertises its pipeline depth.
"""
from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_cache import CompileCache, default_cache
from repro.core.telemetry import LaunchRecord, Timer
from repro.obs.trace import TRACER


def _tree_ready(tree: Any) -> bool:
    return all(l.is_ready() for l in jax.tree_util.tree_leaves(tree)
               if hasattr(l, "is_ready"))


def _pad_rows(x: Any, pad: int) -> Any:
    """``x`` with ``pad`` zero rows appended on the task axis (host arrays
    stay on the host)."""
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.concatenate([x, xp.zeros((pad,) + x.shape[1:], x.dtype)])


def concat_outputs(parts: list) -> Any:
    """Concatenate per-wave (or per-shard) outputs along the task axis —
    the ONE merge semantics shared by the policy driver's wave concat and
    the distributed backend's shard assembly."""
    if len(parts) == 1:
        return parts[0]
    if isinstance(parts[0], list):   # serial scheduler: per-task out lists
        return [o for p in parts for o in p]
    return jax.tree_util.tree_map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *parts)


class WaveHandle:
    """One in-flight wave: outputs may still be computing on device.

    Failure-aware subclasses (the distributed fabric's composite handle)
    set ``can_fail = True`` and may return True from ``failed()`` once the
    wave can no longer complete on its own (a shard is stranded on a dead
    node). The policy driver treats ``failed()`` as an immediate
    re-dispatch signal — no outlier threshold — and never hard-blocks on
    a ``can_fail`` handle it has not seen become ready."""

    can_fail = False          # in-process waves cannot lose a node

    def __init__(self, out: Any, rec: LaunchRecord, t0: float):
        self.out = out
        self.rec = rec
        self.t0 = t0                      # perf_counter at dispatch
        self._t_first: Optional[float] = None
        self._harvested = False

    @classmethod
    def done(cls, out: Any, rec: LaunchRecord, t0: float) -> "WaveHandle":
        """A wave that completed synchronously (rec timings already set)."""
        h = cls(out, rec, t0)
        h._t_first = rec.t_first_result or None
        h._harvested = True
        return h

    def failed(self) -> bool:
        """True once this wave can NEVER become ready by itself (e.g. its
        node died). In-process waves always return False."""
        return False

    def poll(self) -> bool:
        """Non-blocking readiness check; notes time-to-first-result."""
        if self._harvested:
            return True
        leaves = jax.tree_util.tree_leaves(self.out)
        if self._t_first is None:
            for l in leaves:
                if not hasattr(l, "is_ready") or l.is_ready():
                    self._t_first = time.perf_counter() - self.t0
                    break
        return _tree_ready(leaves)

    def result(self) -> tuple:
        """Block until the wave completes; returns (out, LaunchRecord)."""
        if not self._harvested:
            with TRACER.child("backend.result"):
                leaves = jax.tree_util.tree_leaves(self.out)
                if self._t_first is None and leaves:
                    first = leaves[0]
                    if hasattr(first, "block_until_ready"):
                        first.block_until_ready()
                    self._t_first = time.perf_counter() - self.t0
                jax.block_until_ready(self.out)
                self.rec.t_spawn = time.perf_counter() - self.t0
                self.rec.t_first_result = (self._t_first
                                           if self._t_first is not None
                                           else self.rec.t_spawn)
                self._harvested = True
        return self.out, self.rec

    def abandon(self):
        """Finalize this attempt's record WITHOUT blocking on the device.

        Used when a speculative re-dispatch won the race: the losing
        attempt's cost must stay visible in the report, but the driver
        must not barrier on outputs nobody will consume (the device
        finishes or drops them asynchronously; tasks are idempotent).
        Timings are best-effort: t_spawn is the wall clock up to the
        moment of abandonment."""
        if not self._harvested:
            now = time.perf_counter()
            self.rec.t_spawn = now - self.t0
            self.rec.t_first_result = (self._t_first
                                       if self._t_first is not None
                                       else self.rec.t_spawn)
            self.rec.extra["abandoned"] = True
        return self.rec


@runtime_checkable
class LaunchBackend(Protocol):
    """What the policy layer (``core.llmr``) needs from a launcher."""

    name: str
    max_in_flight: int

    def dispatch(self, fn: Callable, chunk: Any, n: int) -> WaveHandle: ...

    def launch(self, fn: Callable, inputs: Any, n: int) -> tuple: ...

    # Backends whose waves have a node/core hierarchy additionally set
    # ``supports_lane_override = True`` and accept a per-dispatch
    # ``inner_lanes=`` keyword (used by wave autoscaling).
    #
    # Multi-host backends (``repro.dist.DistributedBackend``) grow the
    # protocol upward without changing its surface: ``dispatch`` shards a
    # wave across nodes and returns a COMPOSITE handle that harvests
    # per-node sub-results as they land (partial-wave harvest) and turns
    # ``failed()`` True when a node's heartbeat lease expires mid-wave.
    # They also advertise ``n_nodes`` (alive-node count) so the wave
    # controller can size waves to the fabric's width. Scheduler<->node
    # traffic below that surface is a pluggable wire protocol
    # (``repro.dist.transport``: in-process queues or per-node TCP
    # connections), shard payloads stream ahead of their submits so
    # node-side staging overlaps the previous wave's execution, and the
    # shard split is re-weighted by each node's measured speed — none of
    # which the policy layer sees.


# ----------------------------------------------------------------------
# Serial (VM baseline)
# ----------------------------------------------------------------------

class SerialBackend:
    """Per-instance compile + dispatch (VM-style baseline).

    To model the paper's serial scheduler honestly we defeat jax's compile
    cache per instance by closing over a distinct python constant — each
    submission is a fresh program, as each VM boot is a fresh environment.
    """

    name = "serial-vm"
    max_in_flight = 1

    def __init__(self, per_task_overhead_s: float = 0.0):
        self.per_task_overhead_s = per_task_overhead_s

    def launch(self, fn: Callable, inputs: Any, n: int,
               per_task_overhead_s: Optional[float] = None) -> tuple:
        overhead = (self.per_task_overhead_s if per_task_overhead_s is None
                    else per_task_overhead_s)
        rec = LaunchRecord(self.name, n)
        rec.fanout = {"sched": n, "node": 1, "core": 1}
        t = Timer()
        t0 = time.perf_counter()
        outs = []
        for i in range(n):
            item = jax.tree_util.tree_map(lambda x: x[i], inputs)
            salt = i  # defeats the compile cache: a new program per instance

            def inst(x, _s=salt):
                return fn(x), jnp.asarray(_s)

            # the per-task scheduler interaction — trace+lower+compile of
            # a fresh program plus any modeled submit latency — is exactly
            # the cost the paper's ONE array submission eliminates; it
            # must show up in t_schedule, not hide inside t_spawn
            ts = time.perf_counter()
            compiled = jax.jit(inst).lower(item).compile()
            rec.t_schedule += time.perf_counter() - ts
            outs.append(jax.block_until_ready(compiled(item))[0])
            if i == 0:
                # execution-side time to the first result (its submit cost
                # is under t_schedule), so sched/node/core partition the
                # wall clock exactly
                rec.t_first_result = (time.perf_counter() - t0
                                      - rec.t_schedule)
            if overhead:
                time.sleep(overhead)
                rec.t_schedule += overhead
        # t_spawn is the execution remainder so `total` (= t_schedule +
        # t_stage + t_spawn) stays the measured wall clock of the loop
        rec.t_spawn = max(t.lap() - rec.t_schedule, 0.0)
        return outs, rec

    def dispatch(self, fn: Callable, chunk: Any, n: int) -> WaveHandle:
        t0 = time.perf_counter()
        outs, rec = self.launch(fn, chunk, n)
        return WaveHandle.done(outs, rec, t0)


# ----------------------------------------------------------------------
# Array job (compile once, one dispatch covers the wave)
# ----------------------------------------------------------------------

class ArrayBackend:
    """One array job per wave: compile once (cached, persistent), dispatch
    all N lanes at once; optional two-level node/core fan-out."""

    name = "llmr-array"
    max_in_flight = 1
    # the policy layer (autoscaling controller) may pick a fan-out per wave
    supports_lane_override = True

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None,
                 task_axis: str = "data",
                 inner_lanes: Optional[int] = None,
                 cache: Optional[CompileCache] = None,
                 donate: bool = False,
                 target_first_result_s: Optional[float] = None):
        if mesh is not None:
            # the wave program shards only its task axis and lets the
            # compiler place the rest (Auto axes; ``jax.make_mesh`` makes
            # Explicit ones, under which trimming a padded wave is an error)
            mesh = jax.sharding.Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(
                    mesh.axis_names))
        self.mesh = mesh
        self.task_axis = task_axis
        self.inner_lanes = inner_lanes
        self.cache = cache if cache is not None else default_cache()
        # buffer donation is a no-op (warning) on CPU backends
        self.donate = donate and jax.default_backend() != "cpu"
        # the user-facing interactivity SLO: a wave controller built over
        # this backend adopts it as its t_first ceiling, so ONE knob gates
        # serve-side admission preemption AND launch-side wave sizing
        self.target_first_result_s = target_first_result_s
        self._warned_lane_fallback = False

    # -- general-purpose AOT compile through the shared cache -------------
    def compile(self, fn: Callable, example_args: tuple,
                extras: tuple = (), donate_argnums: tuple = ()) -> tuple:
        """(compiled, source): serve + launch share this entry point."""
        return self.cache.compile(fn, example_args, mesh=self.mesh,
                                  donate_argnums=donate_argnums,
                                  extras=extras)

    # -- wave planning ----------------------------------------------------
    def _plan(self, n: int, inner_lanes: Optional[int] = None) -> tuple:
        """-> (outer, inner, fell_back): node x core fan-out of a wave.

        ``fell_back`` is True when a requested ``inner_lanes`` does not
        divide the wave and the plan degrades to a flat ``(n, 1)`` vmap —
        the caller records the dropped fan-out config instead of silently
        discarding it."""
        inner = self.inner_lanes if inner_lanes is None else inner_lanes
        if inner and inner > 1:
            if n % inner == 0:
                return n // inner, inner, False
            return n, 1, True
        return n, 1, False

    def _compile_wave(self, fn: Callable, chunk: Any, n: int,
                      inner_lanes: Optional[int] = None) -> tuple:
        outer, inner, fell_back = self._plan(n, inner_lanes)
        requested = self.inner_lanes if inner_lanes is None else inner_lanes
        if fell_back and not self._warned_lane_fallback:
            warnings.warn(
                f"inner_lanes={requested} does not divide wave size {n}; "
                f"falling back to flat ({n}, 1) fan-out — the node/core "
                f"hierarchy you configured is NOT in effect for such waves",
                RuntimeWarning, stacklevel=3)
            self._warned_lane_fallback = True
        if inner > 1:
            mapped = jax.vmap(jax.vmap(fn))
            chunk = jax.tree_util.tree_map(
                lambda x: x.reshape((outer, inner) + x.shape[1:]), chunk)
        else:
            mapped = jax.vmap(fn)
        in_shardings = None
        if self.mesh is not None:
            # a wave the task axis does not divide is padded up to it (the
            # pad lanes' outputs are dropped): every wave runs on the mesh
            pad = (-outer) % self.mesh.shape[self.task_axis]
            if pad:
                chunk = jax.tree_util.tree_map(
                    lambda x: _pad_rows(x, pad), chunk)
                outer += pad
            sh = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(self.task_axis))
            in_shardings = jax.tree_util.tree_map(lambda _: sh, chunk)
        compiled, source = self.cache.compile(
            mapped, (chunk,), key_fn=fn, mesh=self.mesh,
            in_shardings=in_shardings,
            donate_argnums=(0,) if self.donate else (),
            extras=("wave", outer, inner))
        return compiled, source, chunk, (outer, inner, fell_back, requested)

    # -- LaunchBackend ----------------------------------------------------
    def dispatch(self, fn: Callable, chunk: Any, n: int,
                 inner_lanes: Optional[int] = None) -> WaveHandle:
        """Enqueue one wave. Under JAX async dispatch this returns as soon
        as the program is submitted; the WaveHandle's outputs are futures.
        ``inner_lanes`` overrides the backend default for THIS wave (the
        autoscaling controller re-plans the node/core fan-out per wave)."""
        with TRACER.child("dispatch", where="driver", attrs={"n": n}):
            rec = LaunchRecord(self.name, n)
            t = Timer()
            with TRACER.child("backend.compile_lookup"):
                compiled, source, staged, plan = self._compile_wave(
                    fn, chunk, n, inner_lanes)
            outer, inner, fell_back, requested = plan
            rec.t_schedule = t.lap()      # the ONE scheduler interaction
            rec.extra["compile_source"] = source
            rec.extra["compile_cached"] = source != "compiled"
            if fell_back:
                rec.extra["inner_lanes_fallback"] = {
                    "requested": requested, "wave": n,
                    "used": (outer, inner)}
            rec.fanout = {"sched": 1, "node": outer, "core": inner}
            t0 = time.perf_counter()
            with TRACER.child("backend.enqueue"):
                out = compiled(staged)
                if inner > 1:             # un-nest node/core axes (async)
                    out = jax.tree_util.tree_map(
                        lambda x: x.reshape((outer * inner,) + x.shape[2:]),
                        out)
                if outer * inner != n:    # drop the mesh-padding lanes
                    out = jax.tree_util.tree_map(lambda x: x[:n], out)
            rec.t_dispatch = time.perf_counter() - t0
            return WaveHandle(out, rec, t0)

    def launch(self, fn: Callable, inputs: Any, n: int) -> tuple:
        return self.dispatch(fn, inputs, n).result()


# ----------------------------------------------------------------------
# Pipelined (async double-buffered waves)
# ----------------------------------------------------------------------

class PipelinedBackend(ArrayBackend):
    """ArrayBackend + overlap: advertises ``depth`` waves in flight, so the
    policy driver materializes, stages, and enqueues wave k+1 while wave k
    is still executing on device, and harvests by readiness polling instead
    of a per-wave ``block_until_ready`` barrier. ``dispatch`` itself is the
    inherited non-blocking enqueue (JAX async dispatch); off-CPU, wave
    input buffers are donated so the two in-flight waves double-buffer."""

    name = "llmr-pipelined"

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None,
                 task_axis: str = "data",
                 inner_lanes: Optional[int] = None,
                 cache: Optional[CompileCache] = None,
                 depth: int = 2,
                 donate: bool = True,
                 target_first_result_s: Optional[float] = None):
        super().__init__(mesh=mesh, task_axis=task_axis,
                         inner_lanes=inner_lanes, cache=cache, donate=donate,
                         target_first_result_s=target_first_result_s)
        self.max_in_flight = max(1, depth)


# ----------------------------------------------------------------------
# Factory
# ----------------------------------------------------------------------

BACKENDS = {"serial": SerialBackend, "array": ArrayBackend,
            "pipelined": PipelinedBackend, "dist": None}  # dist: lazy


def make_backend(kind: str, mesh: Optional[jax.sharding.Mesh] = None,
                 cache: Optional[CompileCache] = None,
                 **kwargs) -> LaunchBackend:
    """'serial' | 'array' | 'pipelined' | 'dist' -> a ready LaunchBackend.

    For 'serial', ``mesh``/``cache`` are accepted but meaningless (the
    per-instance VM baseline uses neither); any other kwargs are passed
    through, so unsupported options fail loudly instead of being dropped.
    ``inner_lanes="auto"`` defers the node/core fan-out to the policy
    layer's ``WaveController`` (the backend keeps no static default and
    each wave's lanes arrive via ``dispatch(..., inner_lanes=...)``).
    'dist' resolves lazily to the multi-host fabric
    (``repro.dist.DistributedBackend``; pass ``n_nodes=``/``nodes=``).
    """
    if kind == "serial":
        return SerialBackend(**kwargs)
    if kwargs.get("inner_lanes") == "auto":
        kwargs["inner_lanes"] = None     # per-wave override drives fan-out
    if kind == "dist":
        from repro.dist.backend import DistributedBackend
        return DistributedBackend(mesh=mesh, cache=cache, **kwargs)
    cls = BACKENDS.get(kind)
    if cls is None:
        raise ValueError(f"unknown backend {kind!r}; "
                         f"choose from {sorted(BACKENDS)}")
    return cls(mesh=mesh, cache=cache, **kwargs)
