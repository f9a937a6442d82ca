"""Compile the main-path kernels for a described TPU v5e chip.

Interpret mode runs a Pallas kernel's body as traced JAX ops, so it checks
numerics but never the TPU tiling rules or VMEM limits that Mosaic
enforces. These tests lower and compile each kernel of the calibration,
compression and serving path at SmolLM-135M's published widths (d 576,
9 query / 3 KV heads of dim 64, d_ff 1536, 16-token pages) for a v5e
topology that is described, not attached: what the chip's compiler would
refuse fails here, with no chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import coala
from repro.kernels import chunked_prefill as cp
from repro.kernels import gram_accum as ga
from repro.kernels import paged_attention as pa

CFG = get_config("smollm_135m")
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
BLOCK_SIZE = 16
NUM_BLOCKS = 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip writes a persistent-cache entry it
    # cannot read back without the chip; keep the cache out of the way
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pages(sharding, dtype):
    return _sds((NUM_BLOCKS, HKV, BLOCK_SIZE, HD), dtype, sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("batch,blocks", [(1, 8), (8, 128)])
def test_paged_attention_compiles(one_chip, dtype, batch, blocks):
    fn = jax.jit(lambda *a: pa.paged_attention(*a))
    compiled = fn.lower(
        _sds((batch, HQ, HD), dtype, one_chip),
        _pages(one_chip, dtype), _pages(one_chip, dtype),
        _sds((batch, blocks), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("batch,length,blocks", [(1, 128, 8),
                                                 (8, 1088, 128)])
def test_chunked_prefill_compiles(one_chip, dtype, batch, length, blocks):
    fn = jax.jit(lambda *a: cp.chunked_prefill(*a))
    compiled = fn.lower(
        _sds((batch, length, HQ, HD), dtype, one_chip),
        _pages(one_chip, dtype), _pages(one_chip, dtype),
        _sds((batch, blocks), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip),
        _sds((batch,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


def test_gram_accum_compiles(one_chip):
    fn = jax.jit(lambda a: ga.gram_accum(a))
    compiled = fn.lower(_sds((4096, CFG.d_ff), jnp.bfloat16,
                             one_chip)).compile()
    _assert_kernel(compiled)


def test_coala_solve_compiles(one_chip):
    """Algorithm 1's solve for the widest layer (down: 1536 -> 576) and
    the streaming-QR fold that feeds it, at full float32 precision."""
    n, m = CFG.d_ff, CFG.d_model
    with jax.default_matmul_precision("highest"):
        coala._factor_from_r.lower(
            _sds((m, n), jnp.float32, one_chip),
            _sds((n, n), jnp.float32, one_chip), r=128).compile()
        jax.jit(coala.tsqr_lib.stack_qr).lower(
            _sds((n, n), jnp.float32, one_chip),
            _sds((8192, n), jnp.float32, one_chip)).compile()
