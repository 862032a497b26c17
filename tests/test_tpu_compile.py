"""Compile rehearsal for the TPU v5e: the paged-attention kernel at
qwen3-14b widths, compiled by the chip's own compiler for a described
(not attached) v5e, so a block shape Mosaic refuses fails here rather than
on the first request served on the chip. Nothing runs; this says nothing
about speed or results."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention

# qwen3-14b attention widths (configs/qwen3_14b.py)
H, K, D = 40, 8, 128
SLOTS, PS, VCAP = 8, 16, 2048
NPPS = VCAP // PS
POOL_PAGES = SLOTS * NPPS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(one_chip, S, block_q, slots=SLOTS, pool_pages=POOL_PAGES):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (sds((slots, S, H, D), jnp.bfloat16),
            sds((pool_pages, K, PS, D), jnp.bfloat16),
            sds((pool_pages, K, PS, D), jnp.bfloat16),
            sds((pool_pages, PS), jnp.int32),
            sds((slots, NPPS), jnp.int32),
            sds((slots, S), jnp.int32))
    fn = jax.jit(lambda q, k, v, kp, t, qp: paged_attention(
        q, k, v, kp, t, qp, block_q=block_q))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("S,block_q", [(1, 128), (1024, 128)],
                         ids=["decode", "prefill_1024"])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          S, block_q):
    compiled = _compile(one_chip, S, block_q)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_form_compiles_for_v5e(one_chip, no_persistent_cache):
    """The decode form (S == 1: a grid of slot x page block, pages copied
    by hand) at the qwen3-14b-stage engine's sizes: 16 slots, a pool of
    2,048 pages."""
    compiled = _compile(one_chip, 1, 128, slots=16, pool_pages=2048)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
