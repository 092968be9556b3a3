"""Unified decoder-only LM: dense GQA (granite/qwen/llama/pixtral-backbone)
and uniform-MoE (llama4-family) architectures.

Layers are *scanned*: per-layer params are stacked on a leading axis, the
transformer body compiles once regardless of depth, and remat is applied to
the layer body (checkpointing policy = dots_with_no_batch_dims_saveable by
default — tuned in the perf pass).

Entry points:
    init(cfg, key)                         -> params
    forward(cfg, params, tokens, ...)      -> logits        (train/prefill)
    loss_fn(cfg, params, batch)            -> scalar
    init_cache(cfg, batch, cache_len)      -> decode cache
    decode_step(cfg, params, cache, tok)   -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.partition import current_mesh, shard_hint
from . import common, moe as moe_mod
from .common import Params
from .config import ArchConfig


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _layer_init(cfg: ArchConfig, key, is_moe: bool) -> Params:
    ka, km, kn = jax.random.split(key, 3)
    p: Params = {
        "attn_norm": common.rmsnorm_init(cfg.d_model),
        "mlp_norm": common.rmsnorm_init(cfg.d_model),
        "attn": common.attention_init(
            ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.qkv_bias
        ),
    }
    if is_moe:
        p["moe"] = moe_mod.moe_init(
            km, cfg.d_model, cfg.d_ff, cfg.moe_experts, cfg.moe_shared_expert
        )
    else:
        p["mlp"] = common.swiglu_init(km, cfg.d_model, cfg.d_ff)
    return p


def init(cfg: ArchConfig, key) -> Params:
    ke, kl, kh = jax.random.split(key, 3)
    is_moe = cfg.moe_experts > 0 and cfg.moe_every == 1
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(cfg, k, is_moe))(layer_keys)
    p = {
        "embed": common.embed_init(ke, cfg.padded_vocab, cfg.d_model),
        "layers": layers,
        "final_norm": common.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = {"w": common.dense_init(kh, cfg.d_model, cfg.padded_vocab)}
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _layer_apply(
    cfg: ArchConfig, p: Params, x: jax.Array, positions: jax.Array, window: int
) -> Tuple[jax.Array, jax.Array]:
    h, _ = common.attention(
        p["attn"],
        common.rmsnorm(p["attn_norm"], x),
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv_heads,
        head_dim=cfg.hd,
        positions=positions,
        causal=True,
        window=window,
        rope_theta=cfg.rope_theta,
    )
    x = x + h
    x = shard_hint(x, "batch", "sp", "none")
    aux = jnp.zeros((3,), jnp.float32)
    if "moe" in p:
        m, auxd = moe_mod.moe_dispatch_auto(
            p["moe"], common.rmsnorm(p["mlp_norm"], x), cfg, mesh=current_mesh()
        )
        aux = jnp.stack([auxd["load_balance"], auxd["router_z"], auxd["drop_fraction"]])
    else:
        m = common.swiglu(p["mlp"], common.rmsnorm(p["mlp_norm"], x))
    x = x + m
    x = shard_hint(x, "batch", "sp", "none")
    return x, aux


def forward(
    cfg: ArchConfig,
    params: Params,
    tokens: jax.Array,  # [B, T]
    patch_embeds: Optional[jax.Array] = None,  # [B, Nv, d] (pixtral stub)
    window: int = 0,
    remat: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B, T_total, vocab], aux[3])."""
    adt = jnp.dtype(cfg.act_dtype)
    x = common.embed(params["embed"], tokens).astype(adt)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    B, T, _ = x.shape
    x = shard_hint(x, "batch", "sp", "none")
    positions = jnp.arange(T)

    body = functools.partial(_layer_apply, cfg, window=window, positions=positions)

    def cast_body(lp, y):
        return body(common.cast_tree(lp, adt), y)

    ckpt = functools.partial(
        jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable
    )

    period = max(1, cfg.remat_period)

    def period_body(lps, y):
        aux = jnp.zeros((3,), jnp.float32)
        for i in range(period):
            lp = jax.tree.map(lambda a: a[i], lps)
            y, aux_i = cast_body(lp, y)
            aux = aux + aux_i
        return y, aux

    def scan_body(carry, lps):
        y, aux = (ckpt(period_body) if remat else period_body)(lps, carry)
        # keep the saved carry in the activation dtype — barrier stops XLA
        # from hoisting an f32 convert of the whole residual stack
        y = jax.lax.optimization_barrier(y)
        return y, aux

    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    stacked = jax.tree.map(
        lambda a: a.reshape((cfg.n_layers // period, period) + a.shape[1:]),
        params["layers"],
    )
    x, auxs = jax.lax.scan(scan_body, x, stacked, unroll=cfg.scan_unroll)
    x = shard_hint(x, "batch", None, "none")  # re-gather sp for the head
    x = common.rmsnorm(common.cast_tree(params["final_norm"], adt), x)
    if "head" in params:
        logits = x @ params["head"]["w"].astype(adt)
    else:
        logits = common.unembed(common.cast_tree(params["embed"], adt), x)
    logits = shard_hint(logits, "batch", None, "vocab")
    return logits, jnp.sum(auxs, axis=0)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
    logits, aux = forward(
        cfg, params, batch["tokens"], patch_embeds=batch.get("patches")
    )
    nv = 0 if batch.get("patches") is None else batch["patches"].shape[1]
    logits = logits[:, nv:]
    # mask out the padded vocab tail
    if cfg.padded_vocab != cfg.vocab:
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    loss = common.cross_entropy(logits, batch["labels"], batch.get("loss_mask"))
    if cfg.moe_experts:
        loss = loss + 0.01 * aux[0] + 0.001 * aux[1]
    return loss


# ---------------------------------------------------------------------------
# decode (serve) path
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ArchConfig, batch: int, cache_len: int, fill_len: Optional[int] = None
) -> Params:
    """``cache_len`` slots; ``len`` = tokens already present (serve shapes
    lower with a full cache; real serving starts at fill_len=0)."""
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, cache_len, cfg.hd)
    adt = jnp.dtype(cfg.act_dtype)
    fill = cache_len if fill_len is None else fill_len
    return {
        "k": jnp.zeros(shape, adt),
        "v": jnp.zeros(shape, adt),
        "len": jnp.zeros((), jnp.int32) + fill,
    }


def decode_step(
    cfg: ArchConfig,
    params: Params,
    cache: Params,
    token: jax.Array,  # [B] current token ids
    window: int = 0,
) -> Tuple[jax.Array, Params]:
    """One token for every sequence in the batch, attending over the cache."""
    adt = jnp.dtype(cfg.act_dtype)
    x = common.embed(params["embed"], token[:, None]).astype(adt)  # [B, 1, d]
    x = shard_hint(x, "batch", None, "none")
    pos = cache["len"][None]

    def body(carry, xs):
        y = carry
        lp, ck, cv = xs
        lp = common.cast_tree(lp, adt)
        h, new_kv = common.attention(
            lp["attn"],
            common.rmsnorm(lp["attn_norm"], y),
            n_heads=cfg.n_heads,
            n_kv=cfg.n_kv_heads,
            head_dim=cfg.hd,
            positions=pos,
            causal=True,
            window=window,
            rope_theta=cfg.rope_theta,
            cache=(ck, cv),
            kv_valid=jnp.minimum(cache["len"] + 1, ck.shape[2]),
        )
        y = y + h
        if "moe" in lp:
            m, _ = moe_mod.moe_dispatch_auto(
                lp["moe"], common.rmsnorm(lp["mlp_norm"], y), cfg,
                mesh=current_mesh(),
            )
        else:
            m = common.swiglu(lp["mlp"], common.rmsnorm(lp["mlp_norm"], y))
        y = y + m
        return y, new_kv

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.scan_unroll,
    )
    x = common.rmsnorm(common.cast_tree(params["final_norm"], adt), x)
    if "head" in params:
        logits = x @ params["head"]["w"].astype(adt)
    else:
        logits = common.unembed(common.cast_tree(params["embed"], adt), x)
    new_cache = {"k": nk, "v": nv, "len": cache["len"] + 1}
    return logits[:, 0], new_cache
