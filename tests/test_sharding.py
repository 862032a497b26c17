"""Partition-rule resolution properties (divisibility, priority, fallback),
with hypothesis over shapes."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.sharding.partition import (ACT_RULES, PARAM_RULES, cache_sharding,
                                      param_sharding, resolve_spec)


def mesh2(data=4, model=2):
    # spec resolution needs only axis names and sizes: an abstract mesh
    # stands in for 8 devices this process does not have
    from jax.sharding import AbstractMesh
    return AbstractMesh((data, model), ("data", "model"))


def test_divisible_dims_get_sharded():
    m = mesh2()
    spec = resolve_spec((8, 16), ("d_model", "ff"), m, PARAM_RULES)
    assert spec == P("data", "model")


def test_indivisible_dim_falls_back_to_replication():
    m = mesh2()
    spec = resolve_spec((8, 15), ("d_model", "ff"), m, PARAM_RULES)
    assert spec == P("data", None)


def test_heads_fallback_to_seq():
    """qwen3 pattern: heads not divisible -> seq takes the model axis."""
    m = mesh2()
    spec = resolve_spec((8, 64, 9, 16), ("batch", "seq", "heads", "head_dim"),
                        m, ACT_RULES["train"])
    assert spec[2] is None and spec[1] == "model"


def test_heads_win_over_seq_when_divisible():
    m = mesh2()
    spec = resolve_spec((8, 64, 8, 16), ("batch", "seq", "heads", "head_dim"),
                        m, ACT_RULES["train"])
    assert spec[2] == "model" and spec[1] is None


def test_cache_batch1_falls_back_to_seq_sharding():
    m = mesh2()
    # long_500k: batch=1 cannot shard -> kv_heads takes `model` (divisible on
    # this small mesh) and cache_seq picks up `data`
    spec = resolve_spec((1, 1024, 8, 64),
                        ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
                        m, ACT_RULES["serve"])
    assert spec[0] is None
    assert spec[1] == "data" and spec[2] == "model"
    # with kv_heads indivisible (the 16-way production case) cache_seq takes both
    spec = resolve_spec((1, 1024, 3, 64),
                        ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
                        m, ACT_RULES["serve"])
    assert spec[1] == ("data", "model") and spec[2] is None


@given(d0=st.integers(1, 64), d1=st.integers(1, 64))
@settings(max_examples=40, deadline=None)
def test_resolution_always_divides(d0, d1):
    m = mesh2()
    spec = resolve_spec((d0, d1), ("d_model", "ff"), m, PARAM_RULES)
    mesh_shape = dict(zip(("data", "model"), (4, 2)))
    for dim, part in zip((d0, d1), spec):
        if part is None:
            continue
        axes = part if isinstance(part, tuple) else (part,)
        k = int(np.prod([mesh_shape[a] for a in axes]))
        assert dim % k == 0


def test_no_axis_reused_within_tensor():
    m = mesh2()
    spec = resolve_spec((8, 8, 8), ("experts", "d_model", "ff"), m,
                        PARAM_RULES)
    used = []
    for part in spec:
        if part is None:
            continue
        used += list(part) if isinstance(part, tuple) else [part]
    assert len(used) == len(set(used))


def test_param_tree_sharding_covers_all_archs():
    from repro.configs import ARCHS, get_config
    from repro.models.lm import lm_init
    m = mesh2()
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        shapes = jax.eval_shape(lambda c=cfg: lm_init(jax.random.PRNGKey(0), c))
        tree = param_sharding(shapes, m)
        n = len(jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: hasattr(x, "spec")))
        assert n == len(jax.tree_util.tree_leaves(shapes))
