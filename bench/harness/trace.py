"""Reduce a profiler trace to device busy time, per-name device time and
idle gaps attributed to what the harness was doing.

Two steps, so that the second can be checked on a small recorded trace:

1. ``load_xspace(path)`` flattens the ``.xplane.pb`` that
   ``jax.profiler`` writes into plain event rows
   ``{"plane", "line", "name", "start", "dur"}`` (ns): the ops
   and modules of every device plane, and the harness's own host
   annotations (names starting ``bench.``).
2. ``reduce(events, t0, t1)`` computes, over the window ``[t0, t1]``:

   * ``busy_s``: the union of the intervals in which an op ran, per
     device, averaged over the devices;
   * ``op_s`` / ``module_s``: device self seconds per op (named
     ``<executable>/<HLO instruction>``; a loop op less the ops of its
     body) and seconds per executable, summed over devices, with
     ``module_n`` the number of executions;
   * ``kernel_s(name, module=...)``: device seconds of ops whose name
     contains a kernel's name, optionally only inside executables whose
     name contains ``module``;
   * ``idle_gaps``: every gap between busy intervals, named by the
     harness annotation that overlaps it most (``host:untraced`` when
     none does).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
UNTRACED = "host:untraced"


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def base_name(name: str) -> str:
    """``jit_step_fn(123)`` -> ``jit_step_fn``: executions of one program
    share a name."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``: a device op's
    HLO instruction name, without its signature."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def load_xspace(path: str) -> List[dict]:
    """Flatten an ``.xplane.pb`` into event rows (see module doc)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    rows: List[dict] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": (op_name(e.name)
                                          if line.name == OPS_LINE
                                          else e.name),
                                 "start": int(e.start_ns),
                                 "dur": int(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        rows.append({"plane": plane.name, "line": line.name,
                                     "name": e.name,
                                     "start": int(e.start_ns),
                                     "dur": int(e.duration_ns)})
    return rows


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, t0: int, t1: int) -> Tuple[int, int]:
    return max(s, t0), min(e, t1)


@dataclass
class Reduced:
    window_s: float
    devices: List[str]
    busy_s: float                               # mean over devices
    op_s: Dict[str, float] = field(default_factory=dict)
    module_s: Dict[str, float] = field(default_factory=dict)
    module_n: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    _ops: List[dict] = field(default_factory=list, repr=False)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def kernel_s(self, kernel: str, module: Optional[str] = None) -> float:
        """Device seconds of ops named after ``kernel`` (a Pallas kernel's
        ``name``), inside executables whose name contains ``module``."""
        tot = 0
        for op in self._ops:
            if kernel not in op["name"]:
                continue
            if module is not None and module not in op["module"]:
                continue
            tot += op["dur"]
        return tot / 1e9

    def module_seconds(self, part: str) -> Tuple[float, int]:
        """(device seconds, executions) of executables whose name
        contains ``part``."""
        s = sum(v for k, v in self.module_s.items() if part in k)
        n = sum(v for k, v in self.module_n.items() if part in k)
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(events: List[dict], t0: int, t1: int) -> Reduced:
    """Reduce event rows over the window ``[t0, t1]`` (ns)."""
    by_dev_ops: Dict[str, List[dict]] = defaultdict(list)
    by_dev_mods: Dict[str, List[dict]] = defaultdict(list)
    host: List[dict] = []
    for ev in events:
        s, e = _clip(ev["start"], ev["start"] + ev["dur"], t0, t1)
        if e <= s and ev["dur"] > 0:
            continue
        if _is_device_plane(ev["plane"]):
            (by_dev_ops if ev["line"] == OPS_LINE
             else by_dev_mods)[ev["plane"]].append(
                dict(ev, start=s, dur=max(e - s, 0)))
        elif ev["name"].startswith(HOST_PREFIX):
            host.append(dict(ev, start=s, dur=max(e - s, 0)))
    devices = sorted(set(by_dev_ops) | set(by_dev_mods))
    window = max(t1 - t0, 0)
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    ops_tagged: List[dict] = []
    gaps: List[Tuple[str, float]] = []
    busy_total = 0
    host_iv = sorted((h["start"], h["start"] + h["dur"], h["name"])
                     for h in host)
    host_starts = [h[0] for h in host_iv]
    longest = max((h[1] - h[0] for h in host_iv), default=0)
    for dev in devices:
        mods = sorted(by_dev_mods.get(dev, []), key=lambda m: m["start"])
        starts = [m["start"] for m in mods]
        for m in mods:
            module_s[base_name(m["name"])] += m["dur"] / 1e9
            module_n[base_name(m["name"])] += 1
        ops = by_dev_ops.get(dev) or mods
        for op, own in _self_times(by_dev_ops.get(dev, [])):
            i = bisect.bisect_right(starts, op["start"]) - 1
            enclosing = (base_name(mods[i]["name"])
                         if i >= 0 and op["start"] < mods[i]["start"]
                         + mods[i]["dur"] else "")
            op_s[f"{enclosing}/{op['name']}"] += own / 1e9
            ops_tagged.append(dict(op, module=enclosing))
        busy = _union((o["start"], o["start"] + o["dur"]) for o in ops)
        busy_total += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for k in range(0, len(edges), 2):
            gs, ge = edges[k], edges[k + 1]
            if ge > gs:
                lo = bisect.bisect_left(host_starts, gs - longest)
                hi = bisect.bisect_left(host_starts, ge)
                gaps.append((_attribute(host_iv[lo:hi], gs, ge),
                             (ge - gs) / 1e9))
    busy_s = busy_total / len(devices) / 1e9 if devices else 0.0
    return Reduced(window_s=window / 1e9, devices=devices, busy_s=busy_s,
                   op_s=dict(op_s), module_s=dict(module_s),
                   module_n=dict(module_n), idle_gaps=gaps, _ops=ops_tagged)


def _self_times(ops: List[dict]) -> List[Tuple[dict, int]]:
    """Each op with its self time: its duration less that of the ops
    nested in it (a loop op spans the ops of its body)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i]["start"], -ops[i]["dur"]))
    own = {i: ops[i]["dur"] for i in order}
    stack: List[int] = []
    for i in order:
        s = ops[i]["start"]
        while stack and ops[stack[-1]]["start"] + ops[stack[-1]]["dur"] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i]["dur"]
        stack.append(i)
    return [(ops[i], max(own[i], 0)) for i in order]


def _attribute(host_iv: List[Tuple[int, int, str]], s: int, e: int) -> str:
    """Name of the harness span that overlaps ``[s, e]`` most; the
    innermost (latest-starting) one wins a tie."""
    best, best_ov = UNTRACED, 0
    for hs, he, name in host_iv:
        ov = min(he, e) - max(hs, s)
        if ov > 0 and ov >= best_ov:
            best, best_ov = name, ov
    return best
