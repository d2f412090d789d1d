"""Descriptor-driven quantize-dequantize row copy (DMAC + in-flight kv_int8).

The XDMA-style transform stage (DESIGN.md §9) fused into the Pallas
descriptor-copy idiom: the same scalar-prefetched descriptor stream and
double-buffered grid as :mod:`repro.kernels.descriptor_copy`, but each
row passes through the EF-int8 per-256-block symmetric round trip of
:mod:`repro.optim.compress` between the read and the write — the wire
carries int8 payload + one fp32 scale per block, the destination pool
receives dequantized values.

Bit-compatibility contract: for row width a multiple of ``BLOCK`` and
unit-aligned pools, a row's local 256-blocks coincide with the
pool-absolute blocks of :func:`repro.core.transform.kv8_roundtrip`, so
this kernel is value-identical to copying from the round-tripped pool
(the lowered fallback path and the numpy oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.optim.compress import BLOCK

from .descriptor_copy import row_move_call


def _quantize_row(src_ref, dst_ref):
    """Round-trip one row through per-BLOCK int8 scales, then write it.

    Each 256-lane block is a static lane slice of the row. The int8 cast
    is left out: the clipped, rounded quotients are integers in
    [-127, 127], which float32 holds exactly, so the values are those of
    the int8 round trip.
    """
    for b in range(src_ref.shape[-1] // BLOCK):
        lanes = slice(b * BLOCK, (b + 1) * BLOCK)
        blk = src_ref[:, lanes].astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(blk)) / 127.0, 1e-12)
        q = jnp.clip(jnp.round(blk / scale), -127, 127)
        dst_ref[:, lanes] = (q * scale).astype(dst_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_copy(src_idx: jax.Array, dst_idx: jax.Array, src: jax.Array,
                  dst: jax.Array, *, interpret: bool = False) -> jax.Array:
    """dst[dst_idx[i]] = kv8_roundtrip(src[src_idx[i]]) per descriptor i.

    src/dst: (rows, unit) row pools with ``unit % BLOCK == 0`` (each row
    is a whole number of quantization blocks).
    """
    unit = src.shape[1]
    if unit % BLOCK:
        raise ValueError(f"row width {unit} is not a multiple of {BLOCK}")
    return row_move_call(_quantize_row, src_idx, dst_idx, src, dst,
                         interpret=interpret)


def quantize_copy_bucketed(src_idx: jax.Array, dst_idx: jax.Array,
                           src: jax.Array, dst: jax.Array, *,
                           n_bucket: int,
                           interpret: bool = False) -> jax.Array:
    """:func:`quantize_copy` padded to a fixed grid of ``n_bucket`` steps.

    Same pow2-bucket contract as ``descriptor_copy_bucketed``: ``-1``
    padding marks inactive grid steps, so every chain in a signature
    bucket re-enters one compiled kernel.
    """
    n = src_idx.shape[0]
    if n > n_bucket:
        raise ValueError(f"{n} descriptors exceed bucket {n_bucket}")
    if n < n_bucket:
        pad = jnp.full((n_bucket - n,), -1, jnp.int32)
        src_idx = jnp.concatenate([src_idx.astype(jnp.int32), pad])
        dst_idx = jnp.concatenate([dst_idx.astype(jnp.int32), pad])
    return quantize_copy(src_idx, dst_idx, src, dst, interpret=interpret)
