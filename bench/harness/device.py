"""The chip a run measures: presence, identity, peaks and peak memory.

A run that finds no TPU, or fewer chips than its cell asks for, stops
here: no number from another platform is ever printed under a device
metric.
"""
from __future__ import annotations

import json
import os
from typing import List

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


class UnknownDevice(KeyError):
    """The device kind has no entry in the peaks table."""


def require_chips(n: int, platform: str = "tpu") -> List:
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != platform:
        found = devs[0].platform if devs else "none"
        raise NoChip(f"JAX found no {platform} (platform {found!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chip(s); JAX sees {len(devs)}")
    return devs[:n]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no such statistic)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def peaks(kind: str, path: str = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]
