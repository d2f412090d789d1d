"""The bytes and operations each cell's work needs, counted from shapes.

These are what the algorithm needs, not what the program happens to move:
a copy of ``n`` bytes reads ``n`` and writes ``n``; a decode step of a
dense transformer does two operations per weight it multiplies and the
attention over the positions each sequence actually holds.
"""
from __future__ import annotations

import numpy as np


def copy_payload_bytes(lengths, elem_bytes: int) -> int:
    """Payload of a chain: the submitted descriptors' lengths (elements,
    before coalescing) times the element size."""
    return int(np.sum(np.asarray(lengths, np.int64))) * int(elem_bytes)


def copy_needed_bytes(payload_bytes: int) -> int:
    """HBM bytes a copy needs: every payload byte read once, written once."""
    return 2 * int(payload_bytes)


def dense_matmul_params(m: dict) -> int:
    """Weights a dense GQA decoder multiplies per token: the layers'
    projections and MLP, and the output head (tied or not). The embedding
    lookup multiplies nothing and is left out."""
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    kv = m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    ff = m["intermediate_size"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    return m["num_hidden_layers"] * (attn + mlp) + d * m["vocab_size"]


def decode_flops(m: dict, context_lengths) -> float:
    """Operations of one decode step over a batch whose sequences attend to
    ``context_lengths`` positions each (the new token included)."""
    ctx = np.asarray(context_lengths, np.float64)
    h = m["num_attention_heads"]
    hd = m.get("head_dim") or m["hidden_size"] // h
    attn = 4.0 * m["num_hidden_layers"] * h * hd * ctx.sum()
    return 2.0 * dense_matmul_params(m) * ctx.size + attn
