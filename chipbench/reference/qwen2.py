"""Plain float32 reference of a Qwen2 decoder (dense GQA), and the weights
the benchmark serves it with.

The architecture as published (Qwen2, ``model_type: qwen2``): token
embedding; per layer a pre-norm RMSNorm, attention with biased q/k/v
projections, rotary embeddings over the whole head (``rotate_half``
pairing, base ``rope_theta``), grouped query heads (query head ``h`` reads
KV head ``h // (heads / kv_heads)``), causal softmax scaled by
``head_dim ** -0.5``, output projection, residual; then a pre-norm SwiGLU
MLP (``down(silu(gate(x)) * up(x))``) and residual; a final RMSNorm and the
output head, here the transposed embedding (``tie_word_embeddings``).

Departure, in parameterisation only: each RMSNorm weight is stored as its
offset from one (the norm multiplies by ``1 + w``), because the weights
are laid out the way the program under test keeps them. The benchmark
makes the weights (``make_weights``) and hands the same arrays to the
program and to this reference; nothing here comes from the program.

Everything is computed in float32 at ``Precision.HIGHEST``, one layer at a
time (a scan over the stacked layers) over padded sequences: ``hidden``
gives the final-norm states and ``logits`` the head's output for the rows
asked for. With ``fp8=True`` the same functions are the control: every
matrix rounded to float8 (e4m3, one scale per matrix), activations in
bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def dims(m: dict) -> dict:
    h = m["num_attention_heads"]
    return dict(d=m["hidden_size"], h=h, kv=m["num_key_value_heads"],
                hd=m.get("head_dim") or m["hidden_size"] // h,
                ff=m["intermediate_size"], L=m["num_hidden_layers"],
                V=m["vocab_size"], eps=m["rms_norm_eps"],
                theta=m["rope_theta"])


@functools.partial(jax.jit, static_argnames=("shape_key", "dtype"))
def _make(key, *, shape_key, dtype):
    d, h, kv, hd, ff, L, V = shape_key
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    layer = {
        "norm1": {"scale": normal((L, d), 0.1)},
        "norm2": {"scale": normal((L, d), 0.1)},
        "mixer": {
            "wq": normal((L, d, h, hd), d ** -0.5),
            "wk": normal((L, d, kv, hd), d ** -0.5),
            "wv": normal((L, d, kv, hd), d ** -0.5),
            "wo": normal((L, h, hd, d), (h * hd) ** -0.5),
            "bq": normal((L, h, hd), 0.02),
            "bk": normal((L, kv, hd), 0.02),
            "bv": normal((L, kv, hd), 0.02),
        },
        "ffn": {
            "w_gate": normal((L, d, ff), d ** -0.5),
            "w_up": normal((L, d, ff), d ** -0.5),
            "w_down": normal((L, ff, d), ff ** -0.5),
        },
    }
    return {"embed": {"embedding": normal((V, d), 0.05)},
            "stack": {"prefix": [], "slots": (layer,)},
            "final_norm": {"scale": normal((d,), 0.1)}}


def make_weights(key, m: dict, dtype=jnp.bfloat16):
    """All weights in one jitted call on the device, from ``key``."""
    g = dims(m)
    return _make(key, shape_key=(g["d"], g["h"], g["kv"], g["hd"], g["ff"],
                                 g["L"], g["V"]), dtype=jnp.dtype(dtype))


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None].astype(jnp.float32) * inv           # (B, T, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, pos, g, act, mm):
    """One decoder layer; ``act`` is the activation dtype and ``mm`` the
    weight transform (identity for the reference, fp8 for the control)."""
    b, t, _ = x.shape
    h1 = _rms(x.astype(jnp.float32), p["norm1"]["scale"].astype(jnp.float32),
              g["eps"]).astype(act)
    q = jnp.einsum("btd,dhe->bthe", h1, mm(p["mixer"]["wq"]), precision=HI)
    k = jnp.einsum("btd,dke->btke", h1, mm(p["mixer"]["wk"]), precision=HI)
    v = jnp.einsum("btd,dke->btke", h1, mm(p["mixer"]["wv"]), precision=HI)
    q = (q + p["mixer"]["bq"].astype(act)).astype(jnp.float32)
    k = (k + p["mixer"]["bk"].astype(act)).astype(jnp.float32)
    v = (v + p["mixer"]["bv"].astype(act))
    q, k = _rope(q, pos, g["theta"]), _rope(k, pos, g["theta"])
    grp = g["h"] // g["kv"]
    qg = q.reshape(b, t, g["kv"], grp, g["hd"]).astype(act)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(act), precision=HI,
                   preferred_element_type=jnp.float32) * g["hd"] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(act)
    o = jnp.einsum("bkgqs,bskd->bqkgd", pr, v.astype(act), precision=HI)
    o = o.reshape(b, t, g["h"], g["hd"])
    x = x + jnp.einsum("bthe,hed->btd", o, mm(p["mixer"]["wo"]),
                       precision=HI).astype(x.dtype)
    h2 = _rms(x.astype(jnp.float32), p["norm2"]["scale"].astype(jnp.float32),
              g["eps"]).astype(act)
    gate = jnp.einsum("btd,df->btf", h2, mm(p["ffn"]["w_gate"]), precision=HI)
    up = jnp.einsum("btd,df->btf", h2, mm(p["ffn"]["w_up"]), precision=HI)
    y = jnp.einsum("btf,fd->btd", (jax.nn.silu(gate) * up).astype(act),
                   mm(p["ffn"]["w_down"]), precision=HI)
    return x + y.astype(x.dtype)


def _fp8(w, act):
    """Round a matrix to float8 e4m3 with one scale for the matrix."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w)) / 448.0
    q = (w / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(act)


def _mm(act, fp8):
    return (lambda w: _fp8(w, act)) if fp8 else (lambda w: w.astype(act))


def _hidden(weights, tokens, g, act, fp8: bool):
    mm = _mm(act, fp8)
    x = weights["embed"]["embedding"][tokens].astype(jnp.float32)
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def body(x, p):
        return _layer(x, p, pos, g, act, mm), None

    x, _ = jax.lax.scan(body, x, weights["stack"]["slots"][0])
    return _rms(x, weights["final_norm"]["scale"].astype(jnp.float32),
                g["eps"])


def _head(weights, rows, act, fp8: bool):
    w = _mm(act, fp8)(weights["embed"]["embedding"])
    return jnp.einsum("nd,vd->nv", rows.astype(act), w, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("gkey", "fp8"))
def _hidden_jit(weights, tokens, gkey, fp8):
    act = jnp.bfloat16 if fp8 else jnp.float32
    return _hidden(weights, tokens, dict(gkey), act, fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _head_jit(weights, rows, fp8):
    return _head(weights, rows, jnp.bfloat16 if fp8 else jnp.float32, fp8)


def hidden(weights, tokens, m: dict, *, fp8: bool = False):
    """Final-norm hidden states (B, T, d) of token rows (B, T), causal:
    float32 for the reference, or the float8 control."""
    return _hidden_jit(weights, tokens, tuple(sorted(dims(m).items())), fp8)


def logits(weights, rows, *, fp8: bool = False):
    """float32 logits (N, V) of hidden rows (N, d) through the tied head."""
    return _head_jit(weights, rows, fp8)
