"""The speculative descriptor prefetch engine as a manual Pallas pipeline.

This is the paper's §II-C mechanism transliterated to TPU DMA primitives:
while descriptor i's payload streams HBM->VMEM, the copy for descriptor i+1
is already in flight ("the proper request is issued over the AXI manager
interface in the same cycle"), using two VMEM bounce buffers and DMA
semaphores — the classic double-buffered pipeline. `descriptor_copy.py` gets
the same effect implicitly from the Pallas grid pipeliner; this kernel makes
the mechanism explicit and controllable (bounce-buffer depth = the paper's
`prefetch` parameter, clamped to 2..N here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .descriptor_copy import from_row_view, row_view


def _pipeline_kernel(src_idx_ref, dst_idx_ref, src_hbm, dst_in, dst_hbm,
                     scratch, in_sems, out_sems, *, depth: int):
    del dst_in
    n = src_idx_ref.shape[0]

    def start_in(i):
        slot = jax.lax.rem(i, depth)
        pltpu.make_async_copy(
            src_hbm.at[src_idx_ref[i]], scratch.at[slot], in_sems.at[slot]
        ).start()

    # Warmup: issue the first `depth` speculative fetches back to back.
    for j in range(depth):
        @pl.when(j < n)
        def _(j=j):
            start_in(jnp.int32(j))

    def body(i, carry):
        slot = jax.lax.rem(i, depth)
        # Wait for descriptor i's payload...
        pltpu.make_async_copy(
            src_hbm.at[src_idx_ref[i]], scratch.at[slot], in_sems.at[slot]
        ).wait()
        # ...drain it to its destination...
        out_copy = pltpu.make_async_copy(
            scratch.at[slot], dst_hbm.at[dst_idx_ref[i]], out_sems.at[slot])
        out_copy.start()
        out_copy.wait()
        # ...and immediately refill the slot with descriptor i+depth
        # (the speculative next request).
        @pl.when(i + depth < n)
        def _():
            start_in(i + depth)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def prefetched_chain_copy(src_idx: jax.Array, dst_idx: jax.Array,
                          src: jax.Array, dst: jax.Array, *,
                          depth: int = 2, interpret: bool = False):
    """Row-pool copy with an explicit `depth`-deep descriptor prefetch
    pipeline. Semantics match `descriptor_copy` for non-negative indices.

    Pools and bounce buffers are row views (:func:`row_view`), so each
    DMA moves one whole leading-axis row.
    """
    n = src_idx.shape[0]
    src3 = row_view(src, packed=True)
    dst3 = row_view(dst, packed=True)
    depth = max(2, min(depth, max(n, 2)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((depth,) + src3.shape[1:], src3.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    kernel = functools.partial(_pipeline_kernel, depth=depth)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst3.shape, dst3.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(jnp.maximum(src_idx.astype(jnp.int32), 0),
      jnp.maximum(dst_idx.astype(jnp.int32), 0), src3, dst3)
    return from_row_view(out, dst)
