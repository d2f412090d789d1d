"""Mixture-of-experts with sort-based capacity dispatch.

The dispatch plan (which token row goes to which expert slot) is exactly a
descriptor stream in the paper's sense: src = token index, dst = (expert,
slot), weight in `config`. `moe_dispatch_plan` emits that plan; the dense
jnp path executes it with gather/scatter (the Pallas kernel
`repro.kernels.moe_dispatch` consumes the same plan on TPU).

Routing: softmax router, top-k (optionally renormalized), capacity-bounded
with token dropping (GShard-style), shared experts added densely
(DeepSeek-V2), plus load-balance and router-z auxiliary losses.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.distributed import shard
from .layers import dense_init, mlp, init_mlp


class DispatchPlan(NamedTuple):
    """Descriptor streams for token<->expert movement (static shapes).

    Forward stream (dispatch): slot s <- token_idx[s]  (length E*C).
    Inverse stream (combine):  token t <- sum_j inv_weight[t,j] *
                               expert_out[inv_slot[t,j]]  (shape T x k).
    """
    token_idx: jax.Array    # (E*C,) source token row, -1 = empty slot
    weight: jax.Array       # (E*C,) combine weight for the slot
    inv_slot: jax.Array     # (T, k) expert-slot id per token copy, -1 dropped
    inv_weight: jax.Array   # (T, k) combine weight (0 where dropped)
    num_dropped: jax.Array  # () tokens dropped by capacity


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (m.num_experts, d, m.expert_d_ff), cfg.pdtype),
        "w_up": dense_init(ks[2], (m.num_experts, d, m.expert_d_ff), cfg.pdtype),
        "w_down": dense_init(ks[3], (m.num_experts, m.expert_d_ff, d), cfg.pdtype),
    }
    if m.num_shared_experts:
        p["shared"] = init_mlp(ks[4], d,
                               (m.shared_d_ff or m.expert_d_ff) * m.num_shared_experts,
                               cfg.pdtype)
    return p


def capacity(num_tokens: int, m: MoEConfig) -> int:
    c = int(num_tokens * m.experts_per_token * m.capacity_factor
            // m.num_experts)
    return max(8, (c + 7) // 8 * 8)  # pad to 8 for tiling friendliness


def moe_dispatch_plan(router_probs: jax.Array, m: MoEConfig,
                      cap: int) -> DispatchPlan:
    """Build the dispatch descriptor stream from router probabilities.

    router_probs: (T, E) fp32. Returns slots for each of E experts x cap.
    """
    t, e = router_probs.shape
    k = m.experts_per_token
    topv, topi = jax.lax.top_k(router_probs, k)             # (T, k)
    if m.router_norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    flat_expert = topi.reshape(-1)                          # (T*k,)
    flat_weight = topv.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    # Stable sort by expert id; rank within expert = position - group start.
    order = jnp.argsort(flat_expert, stable=True)
    se, stok, sw = flat_expert[order], flat_token[order], flat_weight[order]
    group_start = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    rank = jnp.arange(t * k, dtype=jnp.int32) - group_start[se].astype(jnp.int32)
    keep = rank < cap
    slot = se.astype(jnp.int32) * cap + rank                # (T*k,)
    slot = jnp.where(keep, slot, e * cap)                   # drop -> overflow

    token_idx = jnp.full((e * cap + 1,), -1, jnp.int32).at[slot].set(
        stok, mode="drop")[:-1]
    weight = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(
        sw, mode="drop")[:-1]

    # Inverse plan: scatter each sorted entry's slot back to its (t, j) copy.
    inv_flat = jnp.full((t * k,), -1, jnp.int32).at[order].set(
        jnp.where(keep, slot, -1))
    inv_slot = inv_flat.reshape(t, k)
    inv_weight = jnp.where(inv_slot >= 0, topv, 0.0)
    return DispatchPlan(token_idx, weight, inv_slot, inv_weight,
                        jnp.sum(~keep))


def aux_losses(router_probs: jax.Array, topi: jax.Array, m: MoEConfig,
               router_logits: jax.Array):
    """Switch/GShard load-balance loss + router z-loss."""
    t, e = router_probs.shape
    me = router_probs.mean(axis=0)                               # (E,)
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32).sum(1)   # (T, E)
    ce = onehot.mean(axis=0) * e / m.experts_per_token
    lb = (me * ce).sum() * e * m.aux_loss_weight
    z = jnp.square(jax.nn.logsumexp(router_logits, axis=-1)).mean()
    return lb + m.router_z_weight * z, {"moe_lb": lb, "moe_z": z}


def moe_ffn(params, x: jax.Array, cfg: ModelConfig,
            act_fn: str = "silu") -> Tuple[jax.Array, jax.Array, dict]:
    """x: (B, S, d) -> (y, aux_loss, metrics).

    Under an active mesh with a model axis, dispatch runs expert-parallel in
    shard_map (zero-communication local dispatch + one combine psum —
    EXPERIMENTS.md §Perf-1); otherwise the pure-GSPMD gather path below.
    """
    from repro.distributed import shardlib
    mesh = shardlib.current_mesh()
    m = cfg.moe
    if (mesh is not None and "model" in mesh.shape
            and m.num_experts % mesh.shape["model"] == 0):
        return _moe_ffn_ep(params, x, cfg, act_fn, mesh)
    return _moe_ffn_gspmd(params, x, cfg, act_fn)


def _moe_ffn_ep(params, x: jax.Array, cfg: ModelConfig, act_fn: str, mesh):
    """Expert-parallel MoE: tokens stay on their (pod, data) shard, every
    shard dispatches locally to all experts (per-shard capacity), each
    model-rank computes its E/TP experts, partial token outputs psum over
    the model axis. Dispatch itself moves zero bytes across chips — the
    descriptor plan stays local, exactly the paper's cheap-descriptor thesis.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import shardlib

    m = cfg.moe
    dt = cfg.cdtype
    b, s, d = x.shape
    rules = shardlib.current_rules()
    batch_ax = rules.get("batch")
    if batch_ax is not None:
        axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
        ax_size = 1
        for a in axes:
            ax_size *= mesh.shape.get(a, 1)
        if (b * s) % ax_size != 0:
            batch_ax = None     # e.g. single-sequence long-context decode
    n_model = mesh.shape["model"]
    e_loc = m.num_experts // n_model
    act = jax.nn.silu if act_fn == "silu" else jax.nn.gelu

    def local_fn(xt, router_w, w_gate, w_up, w_down):
        # xt: (T_loc, d); w_*: (E_loc, d, f) — this rank's experts.
        t_loc = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        cap = capacity(t_loc, m)
        plan = moe_dispatch_plan(probs, m, cap)
        topv, topi = jax.lax.top_k(probs, m.experts_per_token)
        aux, metrics = aux_losses(probs, topi, m, logits)

        # Local gather of THIS rank's expert slots only (no communication).
        ridx = jax.lax.axis_index("model")
        slot0 = ridx * e_loc * cap
        own_tokens = jax.lax.dynamic_slice_in_dim(
            plan.token_idx, slot0, e_loc * cap)
        xe = xt[jnp.maximum(own_tokens, 0)].astype(dt)
        xe = xe * (own_tokens >= 0)[:, None].astype(dt)
        xe = xe.reshape(e_loc, cap, d)

        gate = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", act(gate) * up, w_down.astype(dt))
        ye_flat = ye.reshape(e_loc * cap, d)

        # Combine: this rank contributes only its own slots; psum finishes.
        rel = plan.inv_slot - slot0
        own = (rel >= 0) & (rel < e_loc * cap)
        rows = ye_flat[jnp.clip(rel, 0, e_loc * cap - 1)]
        w = jnp.where(own, plan.inv_weight, 0.0)
        y = jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                       rows.astype(jnp.float32)).astype(dt)
        y = jax.lax.psum(y, "model")
        # aux is identical within a data row; average across token shards.
        if batch_ax is not None:
            aux = jax.lax.pmean(aux, batch_ax)
            dropped = jax.lax.pmean(plan.num_dropped / jnp.maximum(t_loc, 1),
                                    batch_ax)
        else:
            dropped = plan.num_dropped / jnp.maximum(t_loc, 1)
        return y, aux, dropped

    t_spec = P(batch_ax, None)
    w_spec = P("model", None, None)
    other_axes = tuple(a for a in mesh.axis_names)
    y, aux, dropped = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(t_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(t_spec, P(), P()),
        check_vma=False,
    )(x.reshape(b * s, d), params["router"],
      params["w_gate"], params["w_up"], params["w_down"])

    if m.num_shared_experts:
        y = y + mlp(params["shared"], x.reshape(b * s, d), act_fn, dt)
    metrics = {"moe_dropped": dropped}
    return y.reshape(b, s, d), aux, metrics


def _moe_ffn_gspmd(params, x: jax.Array, cfg: ModelConfig,
                   act_fn: str = "silu") -> Tuple[jax.Array, jax.Array, dict]:
    m = cfg.moe
    dt = cfg.cdtype
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    cap = capacity(t, m)
    plan = moe_dispatch_plan(probs, m, cap)
    topv, topi = jax.lax.top_k(probs, m.experts_per_token)
    aux, metrics = aux_losses(probs, topi, m, logits)

    # Gather tokens into (E, C, d) — the descriptor-engine gather. Experts
    # shard over the TP axis (EP) and the capacity dim over the data axis,
    # so expert matmuls use the full chip grid (EXPERIMENTS.md §Perf-1).
    safe = jnp.maximum(plan.token_idx, 0)
    xe = xt[safe].reshape(m.num_experts, cap, d).astype(dt)
    xe = xe * (plan.token_idx >= 0).reshape(m.num_experts, cap, 1).astype(dt)
    xe = shard(xe, "experts", "expert_cap", None)

    act = jax.nn.silu if act_fn == "silu" else jax.nn.gelu
    gate = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(dt))
    h = act(gate) * up
    h = shard(h, "experts", "expert_cap", None)
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))
    ye = shard(ye, "experts", "expert_cap", None)

    # Combine via the inverse descriptor stream: gather-and-weight per token
    # (gather keeps GSPMD happy and matches kernels.moe_dispatch on TPU).
    flat_y = ye.reshape(m.num_experts * cap, d)
    rows = flat_y[jnp.maximum(plan.inv_slot, 0)]          # (T, k, d)
    w = jnp.where(plan.inv_slot >= 0, plan.inv_weight, 0.0)
    y = jnp.einsum("tk,tkd->td", w.astype(jnp.float32),
                   rows.astype(jnp.float32)).astype(dt)

    if m.num_shared_experts:
        y = y + mlp(params["shared"], xt, act_fn, dt)

    metrics = dict(metrics, moe_dropped=plan.num_dropped / jnp.maximum(t, 1))
    return y.reshape(b, s, d), aux, metrics
