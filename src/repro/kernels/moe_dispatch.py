"""MoE dispatch/combine kernels driven by the DispatchPlan descriptor streams.

Dispatch is the paper's gather: slot s pulls token row token_idx[s]
(scalar-prefetched, one row-block per grid step). Combine is the inverse
stream: token t pulls its k expert-output rows — realized by passing the
expert-output pool k times, each copy with its own descriptor-driven
index_map, so all k fetches pipeline like speculative descriptor reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .descriptor_copy import row_view


def _gather_kernel(idx_ref, tok_ref, out_ref):
    i = pl.program_id(0)
    active = idx_ref[i] >= 0
    out_ref[...] = jnp.where(active, tok_ref[...], 0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gather(token_idx: jax.Array, tokens: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """Dispatch: (E*C,) descriptor stream gathering (T, d) token rows."""
    n = token_idx.shape[0]
    d = tokens.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((None, 1, d),
                               lambda i, idx: (jnp.maximum(idx[i], 0), 0, 0))],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), tokens.dtype),
        interpret=interpret,
    )(token_idx.astype(jnp.int32), row_view(tokens))
    return out.reshape(n, d)


def _combine_kernel(slot_ref, w_ref, *refs):
    (*expert_refs, out_ref) = refs
    t = pl.program_id(0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j, eref in enumerate(expert_refs):
        active = slot_ref[t, j] >= 0
        w = jnp.where(active, w_ref[t, j], 0.0)
        acc = acc + w * eref[...].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine(inv_slot: jax.Array, inv_weight: jax.Array,
                expert_out: jax.Array, *, interpret: bool = False):
    """Combine: out[t] = sum_j w[t,j] * expert_out[inv_slot[t,j]].

    inv_slot/inv_weight: (T, k); expert_out: (E*C, d) -> (T, d).
    The pool is passed k times, each with a descriptor-driven index_map —
    the k fetches for one token pipeline like the paper's speculative
    descriptor requests.
    """
    t, k = inv_slot.shape
    d = expert_out.shape[1]

    def make_map(j):
        return lambda i, slot, w: (jnp.maximum(slot[i, j], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[pl.BlockSpec((None, 1, d), make_map(j)) for j in range(k)],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, slot, w: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, d), expert_out.dtype),
        interpret=interpret,
    )(inv_slot.astype(jnp.int32), inv_weight.astype(jnp.float32),
      *([row_view(expert_out)] * k))
    return out.reshape(t, d)
