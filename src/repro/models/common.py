"""Shared model components: norms, rotary embeddings, attention, MLPs.

Parameters are plain nested dicts of jnp arrays (pytrees) — no framework.
Every layer exposes ``init(key, cfg) -> params`` and ``apply(params, x, ...)``.
Layer stacks are *scanned* (params stacked on a leading axis) so the dry-run
compiles one layer body regardless of depth.

Sharding: activations get ``with_sharding_constraint`` hints against the
logical rules in ``repro.sharding.partition``; weights are placed by the
in_shardings of the jitted step functions.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

Params = Dict[str, Any]


def cast_tree(tree: Params, dtype) -> Params:
    """Mixed precision: cast f32 compute weights to the activation dtype at
    use sites (master weights stay f32 in the optimizer state).

    The optimization barrier pins the convert *before* any collective that
    consumes the weight: without it XLA hoists converts across all-gathers
    (AG(convert(x)) → convert(AG(x))) and the ZeRO weight gathers travel in
    f32 — 2× the wire bytes (measured on llama4-maverick, EXPERIMENTS.md
    §Perf)."""
    casted = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, tree
    )
    return jax.lax.optimization_barrier(casted)


def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / math.sqrt(d_in))
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    # variance in f32, but cast the inverse BEFORE the x-sized multiply so no
    # f32 tensor of x's shape is ever materialized (keeps the scan residual
    # stack in the activation dtype)
    var = jnp.mean(jnp.square(x).astype(jnp.float32), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * p["scale"].astype(x.dtype)


def layernorm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: [B, H, T, hd]; positions: [T] or [B, T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
        ang = ang[None, None]  # [1, 1, T, half]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, None]  # [B, 1, T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional bias, optional KV cache, causal/window)
# ---------------------------------------------------------------------------


def attention_init(
    key, d_model: int, n_heads: int, n_kv: int, head_dim: int, qkv_bias: bool = False
) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": dense_init(ks[1], d_model, n_kv * head_dim),
        "wv": dense_init(ks[2], d_model, n_kv * head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
    return p


def attention(
    p: Params,
    x: jax.Array,  # [B, T, d]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    window: int = 0,
    rope_theta: float = 10000.0,
    use_rope: bool = True,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (k,v) [B,Hkv,Tc,hd]
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    kv_valid: Optional[jax.Array] = None,  # dynamic count of live kv slots
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Returns (out [B, T, d], new_cache).  Decode: T=1, cache holds history.
    Cross-attention: pass ``cross_kv`` (encoder keys/values), causal=False."""
    B, T, _ = x.shape
    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, T, n_heads, head_dim).transpose(0, 2, 1, 3)

    if cross_kv is not None:
        k, v = cross_kv
        new_cache = None
    else:
        k = x @ p["wk"]
        v = x @ p["wv"]
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = k.reshape(B, T, n_kv, head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, n_kv, head_dim).transpose(0, 2, 1, 3)
        if use_rope:
            pos = positions if positions is not None else jnp.arange(T)
            k = rope(k, pos, rope_theta)
        if cache is not None:
            # ring-buffer append: write the new (rotated) K/V at slot
            # len % M via dynamic_update_slice — no cache-sized copy, donation
            # aliases in place, and SPMD keeps the cache sharding (the
            # concat+slice roll forced involuntary resharding).  Softmax is
            # permutation-invariant over kv slots, so slot order is free.
            ck, cv = cache
            M = ck.shape[2]
            cur_len = (
                positions[0] if positions is not None else jnp.int32(M)
            )
            widx = jnp.mod(cur_len.astype(jnp.int32), M)
            k = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, 0, widx, 0)
            )
            v = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, 0, widx, 0)
            )
            new_cache = (k, v)
        else:
            new_cache = None
    if use_rope and cross_kv is None:
        # explicit positions are authoritative; only the positionless
        # suffix-query case aligns to the kv tail
        if positions is not None:
            pos = positions
        else:
            pos = jnp.arange(T) + (k.shape[2] - T if cache is not None else 0)
        q = rope(q, pos, rope_theta)

    out = kops.flash_attention(
        q, k, v, causal=causal and cache is None, window=window,
        kv_valid=kv_valid,
    )
    out = out.transpose(0, 2, 1, 3).reshape(B, T, n_heads * head_dim)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], d_model, d_ff),
        "wg": dense_init(ks[1], d_model, d_ff),
        "wo": dense_init(ks[2], d_ff, d_model),
    }


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def gelu_mlp_init(key, d_model: int, d_ff: int) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "wi": dense_init(ks[0], d_model, d_ff),
        "bi": jnp.zeros((d_ff,), jnp.float32),
        "wo": dense_init(ks[1], d_ff, d_model),
        "bo": jnp.zeros((d_model,), jnp.float32),
    }


def gelu_mlp(p: Params, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ p["wi"] + p["bi"]) @ p["wo"] + p["bo"]


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------


def embed_init(key, vocab: int, d_model: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return p["table"][tokens]


def unembed(p: Params, x: jax.Array) -> jax.Array:
    return x @ p["table"].T


def cross_entropy(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None
) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
