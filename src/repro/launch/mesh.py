"""Production mesh construction.

A FUNCTION (not module-level constant) so importing never touches jax device
state. Axis semantics: `pod` = cross-pod DCN axis, `data` = batch/FSDP ICI
axis, `model` = tensor/expert-parallel ICI axis. Shapes are configurable so
the same rules drive larger deployments (e.g. (8,16,16) = 2048 chips).

Axes are ``Auto``: the partition rules (``sharding/partition.py``) place
arrays with sharding constraints and let the compiler propagate the rest.
``jax.make_mesh`` defaults to ``Explicit`` axes, under which every
operation's output sharding is typed instead, so the type is spelled out.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         pods: int = 2, data: int = 16, model: int = 16):
    shape = (pods, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a Dx1 (data, model) mesh."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))
