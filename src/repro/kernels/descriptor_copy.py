"""Descriptor-driven row copy — the paper's DMAC as a Pallas TPU kernel.

The descriptor stream (src row, dst row) is passed as *scalar-prefetch*
operands (``pltpu.PrefetchScalarGridSpec``): Pallas materializes them in SMEM
*before* the grid runs and feeds them to the ``BlockSpec.index_map``s, so the
address of step i+1's block is known while step i's payload streams — exactly
the paper's speculative descriptor prefetching, realized with the TPU's
native double-buffered grid pipeline (§II-C; DESIGN.md §2).

Rows are the transfer unit (the fixed "burst"): irregularity lives entirely
in the descriptor index pattern, as in the paged-KV / MoE consumers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_view(pool: jax.Array, *, packed: bool = False) -> jax.Array:
    """View a (rows, unit) pool as (rows, 1, unit).

    The TPU tiles the last two axes of an array. Moving the row axis out
    of them makes one row one block (and one DMA) at any row index; on a
    2-D pool a single row is a slice of an (8, 128) tile, which Mosaic
    refuses. XLA relays the pool out to and from this view, so each
    viewed pool costs one temporary per call, lane-padded to 128.

    ``packed=True`` (for kernels that only move bits) packs each run of
    16- or 8-bit lanes into one uint32 lane. A 16-bit row is then not
    padded to two sublanes, which a manual single-row DMA needs; XLA's
    packing costs more temporaries than the plain view, so the gridded
    kernels do not pack. :func:`from_row_view` undoes either view.
    """
    rows, unit = pool.shape
    k = 4 // pool.dtype.itemsize if pool.dtype.itemsize < 4 else 1
    if packed and k > 1 and unit % k == 0:
        words = jax.lax.bitcast_convert_type(
            pool.reshape(rows, unit // k, k), jnp.uint32)
        return words.reshape(rows, 1, unit // k)
    return pool.reshape(rows, 1, unit)


def from_row_view(view: jax.Array, like: jax.Array) -> jax.Array:
    """The (rows, unit) pool of ``like``'s dtype that ``view`` holds."""
    rows = view.shape[0]
    if view.dtype != like.dtype:
        view = jax.lax.bitcast_convert_type(view.reshape(rows, -1),
                                            like.dtype)
    return view.reshape(rows, like.shape[1])


def fill_inactive(src_idx: jax.Array, dst_idx: jax.Array):
    """Give every inactive (-1) descriptor the move of an active one.

    An output block is written back whether or not the kernel body stored
    to it, so a skipped grid step would write stale VMEM to its row. An
    inactive step instead repeats the nearest earlier active move (or,
    before the first, the first one): the same source row rewritten to
    the same destination with no other write between, which changes
    nothing. Returns ``(src_idx, dst_idx, any_active)``.
    """
    src_idx = src_idx.astype(jnp.int32)
    dst_idx = dst_idx.astype(jnp.int32)
    active = (src_idx >= 0) & (dst_idx >= 0)
    pos = jnp.arange(src_idx.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(active, pos, -1))
    take = jnp.where(last >= 0, last, jnp.argmax(active).astype(jnp.int32))
    return src_idx[take], dst_idx[take], jnp.any(active)


def row_move_call(body, src_idx: jax.Array, dst_idx: jax.Array,
                  src: jax.Array, dst: jax.Array, *,
                  interpret: bool) -> jax.Array:
    """Run ``body(src_row_ref, dst_row_ref)`` once per descriptor.

    Shared by the plain and the transforming row copies: the descriptor
    stream is scalar-prefetched, step i reads row ``src_idx[i]`` and
    writes row ``dst_idx[i]`` of the aliased destination pool, whose
    other rows keep their contents. Inactive descriptors write nothing.
    """
    n = src_idx.shape[0]
    sidx, didx, any_active = fill_inactive(src_idx, dst_idx)
    src3 = row_view(src)
    dst3 = row_view(dst)
    unit = src3.shape[2]

    def kernel(sidx_ref, didx_ref, src_ref, dst_in_ref, dst_ref):
        del sidx_ref, didx_ref, dst_in_ref
        body(src_ref, dst_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, 1, unit), lambda i, s, d: (s[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, 1, unit), lambda i, s, d: (d[i], 0, 0)),
    )

    def run():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(dst3.shape, dst3.dtype),
            input_output_aliases={3: 0},   # dst pool (after 2 scalars + src)
            interpret=interpret,
        )(sidx, didx, src3, dst3)
        return from_row_view(out, dst)

    return jax.lax.cond(any_active, run, lambda: dst)


def _copy_row(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def descriptor_copy(src_idx: jax.Array, dst_idx: jax.Array, src: jax.Array,
                    dst: jax.Array, *, interpret: bool = False) -> jax.Array:
    """dst[dst_idx[i]] = src[src_idx[i]] for each descriptor i.

    src/dst: (rows, unit) row pools of one dtype. A descriptor with a
    negative index on either side is inactive.
    """
    return row_move_call(_copy_row, src_idx, dst_idx, src, dst,
                         interpret=interpret)


# ---------------------------------------------------------------------------
# Bucketed variant: one compiled kernel per pow2 descriptor-count bucket.
# ---------------------------------------------------------------------------

def descriptor_copy_bucketed(src_idx: jax.Array, dst_idx: jax.Array,
                             src: jax.Array, dst: jax.Array, *,
                             n_bucket: int,
                             interpret: bool = False) -> jax.Array:
    """:func:`descriptor_copy` padded to a fixed grid of ``n_bucket`` steps.

    The translation cache (:mod:`repro.runtime.lowering`) keys compiled
    artifacts on pow2 segment-count buckets; padding the index operands
    with ``-1`` (inactive descriptors, which move nothing) makes every
    chain in a bucket re-enter one compiled kernel instead of recompiling
    per exact descriptor count.
    """
    n = src_idx.shape[0]
    if n > n_bucket:
        raise ValueError(f"{n} descriptors exceed bucket {n_bucket}")
    if n < n_bucket:
        pad = jnp.full((n_bucket - n,), -1, jnp.int32)
        src_idx = jnp.concatenate([src_idx.astype(jnp.int32), pad])
        dst_idx = jnp.concatenate([dst_idx.astype(jnp.int32), pad])
    return descriptor_copy(src_idx, dst_idx, src, dst, interpret=interpret)


# ---------------------------------------------------------------------------
# Chained variant: executes a linked list without pre-flattening, using the
# pointer-doubled permutation from repro.core.chain.flatten_chain.
# ---------------------------------------------------------------------------

def chain_copy(descs, src, dst, *, head: int = 0,
               interpret: bool = False) -> jax.Array:
    """Execute a DescriptorArray chain of row moves on the row pools."""
    from repro.core.chain import flatten_chain

    perm, _ = flatten_chain(descs.nxt, head)
    order = jnp.where(perm >= 0, perm, 0)
    gathered_src = jnp.where(perm >= 0, descs.src[order], -1)
    gathered_dst = jnp.where(perm >= 0, descs.dst[order], -1)
    return descriptor_copy(gathered_src, gathered_dst, src, dst,
                           interpret=interpret)
