"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is asked to compile each kernel for a described (not
attached) v5e chip, at the widths the DMA runtime and the qwen2.5-3b serve
path use. Nothing runs: these tests catch what Mosaic refuses (unaligned
slices, unsupported ops, VMEM over-use) without a chip. Interpret-mode
correctness lives in test_kernels.py, test_lowering.py and
test_transform.py.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.descriptor_copy import descriptor_copy_bucketed
from repro.kernels.moe_dispatch import moe_combine, moe_gather
from repro.kernels.paged_attention import paged_attention
from repro.kernels.prefetch_pipeline import prefetched_chain_copy
from repro.kernels.quantize_copy import quantize_copy_bucketed

ROWS = 4096        # pool rows: the kernels' grids do not depend on it
N_DESC = 256       # descriptors per drain (a pow2 bucket)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _copy_args(sharding, dtype, unit):
    idx = _spec(sharding, (N_DESC,), jnp.int32)
    pool = _spec(sharding, (ROWS, unit), dtype)
    return idx, idx, pool, pool


@pytest.mark.parametrize("dtype,unit", [
    (jnp.bfloat16, 256),     # one qwen2.5-3b KV token row (2 x 128)
    (jnp.float32, 16),       # a 64-byte unit, the paper's target size
], ids=["bf16-256", "fp32-16"])
def test_descriptor_copy_bucketed_compiles(one_chip, dtype, unit):
    _compile(lambda s, d, a, b: descriptor_copy_bucketed(
        s, d, a, b, n_bucket=N_DESC, interpret=False),
        *_copy_args(one_chip, dtype, unit))


def test_quantize_copy_bucketed_compiles(one_chip):
    _compile(lambda s, d, a, b: quantize_copy_bucketed(
        s, d, a, b, n_bucket=N_DESC, interpret=False),
        *_copy_args(one_chip, jnp.float32, 256))


def test_moe_gather_compiles(one_chip):
    _compile(lambda i, t: moe_gather(i, t, interpret=False),
             _spec(one_chip, (N_DESC,), jnp.int32),
             _spec(one_chip, (ROWS, 2048), jnp.bfloat16))


def test_moe_combine_compiles(one_chip):
    top_k = 4
    _compile(lambda s, w, e: moe_combine(s, w, e, interpret=False),
             _spec(one_chip, (N_DESC, top_k), jnp.int32),
             _spec(one_chip, (N_DESC, top_k), jnp.float32),
             _spec(one_chip, (ROWS, 2048), jnp.bfloat16))


def test_prefetched_chain_copy_compiles(one_chip):
    _compile(lambda s, d, a, b: prefetched_chain_copy(
        s, d, a, b, depth=4, interpret=False),
        *_copy_args(one_chip, jnp.bfloat16, 256))


def test_paged_attention_compiles_at_qwen2_5_3b_decode_shapes(one_chip):
    batch, heads, kv_heads, head_dim, page, pages, max_pages = \
        4, 16, 2, 128, 16, 512, 8
    kv = _spec(one_chip, (pages, page, kv_heads, head_dim), jnp.bfloat16)
    _compile(lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                   interpret=False),
             _spec(one_chip, (batch, heads, head_dim), jnp.bfloat16), kv, kv,
             _spec(one_chip, (batch, max_pages), jnp.int32),
             _spec(one_chip, (batch,), jnp.int32))
