"""Public entry points for the Pallas kernels.

On TPU the kernels compile to Mosaic; on CPU they run in interpret mode
(the Pallas body executes in Python, for correctness checks). Any other
backend is an error: there is no silent jnp fallback. Model code selects
these via config, defaulting to the jnp reference path for AOT dry-run
lowering (kernel FLOPs == reference FLOPs at the HLO level).

The entry points the DMA runtime drains through record
:data:`LAUNCH_EVENT` through ``jax.monitoring`` with the kernel's name on
every call, so a caller that registers a listener can tell that a drain
went through a kernel and not through a jnp engine.
"""
from __future__ import annotations

import jax
from jax import monitoring

from repro.core.speculation import DEFAULT_POLICY, PolicyLike, static_depth

from . import ref  # noqa: F401  (re-exported oracles)
from .descriptor_copy import chain_copy, descriptor_copy, descriptor_copy_bucketed
from .flash_attention import flash_attention
from .moe_dispatch import moe_combine, moe_gather
from .paged_attention import paged_attention
from .prefetch_pipeline import prefetched_chain_copy
from .quantize_copy import quantize_copy_bucketed

LAUNCH_EVENT = "/repro/kernels/launch"


def _launch(kernel: str) -> None:
    monitoring.record_event(LAUNCH_EVENT, kernel=kernel)


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU or run interpreted on CPU; "
        f"the {backend!r} backend is neither")


def descriptor_copy_op(src_idx, dst_idx, src, dst):
    _launch("descriptor_copy")
    return descriptor_copy(src_idx, dst_idx, src, dst, interpret=_interpret())


def descriptor_copy_bucketed_op(src_idx, dst_idx, src, dst, *, n_bucket: int):
    _launch("descriptor_copy_bucketed")
    return descriptor_copy_bucketed(src_idx, dst_idx, src, dst,
                                    n_bucket=n_bucket, interpret=_interpret())


def quantize_copy_bucketed_op(src_idx, dst_idx, src, dst, *, n_bucket: int):
    _launch("quantize_copy_bucketed")
    return quantize_copy_bucketed(src_idx, dst_idx, src, dst,
                                  n_bucket=n_bucket, interpret=_interpret())


def chain_copy_op(descs, src, dst, head: int = 0):
    return chain_copy(descs, src, dst, head=head, interpret=_interpret())


def flash_attention_op(q, k, v, *, causal=True, window=None,
                       q_block=128, kv_block=128):
    return flash_attention(q, k, v, causal=causal, window=window,
                           q_block=q_block, kv_block=kv_block,
                           interpret=_interpret())


def paged_attention_op(q, k_pages, v_pages, block_tables, lengths):
    return paged_attention(q, k_pages, v_pages, block_tables, lengths,
                           interpret=_interpret())


def moe_gather_op(token_idx, tokens):
    return moe_gather(token_idx, tokens, interpret=_interpret())


def moe_combine_op(inv_slot, inv_weight, expert_out):
    return moe_combine(inv_slot, inv_weight, expert_out,
                       interpret=_interpret())


def prefetched_chain_copy_op(src_idx, dst_idx, src, dst,
                             depth: "PolicyLike | None" = None):
    """Chain copy through the explicit prefetch pipeline (§II-C).

    ``depth`` accepts the legacy int, any
    :class:`repro.core.speculation.SpeculationPolicy`, or ``None`` for the
    shared :data:`repro.core.speculation.DEFAULT_POLICY` — the same source
    of truth the cycle simulator's speculation config uses, so the kernel
    and the simulator cannot silently diverge.
    """
    resolved = static_depth(DEFAULT_POLICY if depth is None else depth)
    return prefetched_chain_copy(src_idx, dst_idx, src, dst, depth=resolved,
                                 interpret=_interpret())
