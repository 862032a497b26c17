"""Plain reference of the launch-16k application: every instance is
``layers`` of ``h = tanh(h @ W)`` over its own argument vector of 64
values, with one shared 64 x 64 W. Float32 throughout, matrix products at
the highest precision; nothing of the launch code is used.
"""
from __future__ import annotations

import numpy as np


def app_weight(app: dict) -> np.ndarray:
    """The application's shared W, fixed by the configuration (the paper
    launches one application binary; only the instances' inputs vary)."""
    import ml_dtypes
    width = int(app["item_shape"][-1])
    rng = np.random.default_rng(int(app["app_seed"]))
    w = rng.standard_normal((width, width), np.float32) / np.sqrt(width)
    return w.astype(ml_dtypes.bfloat16)


def _q8(a):
    """Round to float8_e4m3fn, scaled per tensor into its range."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _forward(h, wf, layers: int, policy: str):
    import jax
    import jax.numpy as jnp
    if policy == "float8":
        wf = _q8(wf)
    for _ in range(layers):
        if policy == "float8":
            h = _q8(h)
        h = jnp.tanh(jnp.matmul(h, wf, precision=jax.lax.Precision.HIGHEST))
    return h


_FWD = None


def control_app(w: np.ndarray, layers: int):
    """The control in the application's place: the reference with W and
    every layer's input rounded to float8_e4m3fn, one instance at a time
    (the launcher maps it over the instances as it maps the application)."""
    import jax.numpy as jnp
    wf = jnp.asarray(np.asarray(w, np.float32))

    def app(x):
        return _forward(x.astype(jnp.float32), wf, layers, "float8")

    return app


def reference(x: np.ndarray, w: np.ndarray, layers: int,
              policy: str = "float32") -> np.ndarray:
    """Float32 forward of instances ``x`` (n, ..., width). ``policy``
    "float8" rounds W and every layer's input to float8_e4m3fn (scaled per
    tensor) first: the control, one precision step below the bfloat16 the
    configuration states."""
    global _FWD
    import jax
    import jax.numpy as jnp
    if _FWD is None:
        _FWD = jax.jit(_forward, static_argnums=(2, 3))
    out = _FWD(jnp.asarray(np.asarray(x, np.float32)),
               jnp.asarray(np.asarray(w, np.float32)), layers, policy)
    return np.asarray(out)


def max_abs_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over every element; inf for a shape mismatch
    or a non-finite output."""
    got = np.asarray(got, np.float32)
    if got.shape != want.shape:
        return float("inf")
    d = np.abs(got - want)
    if not np.all(np.isfinite(d)):
        return float("inf")
    return float(d.max())
