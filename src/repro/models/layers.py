"""Functional building blocks (no framework deps): params are plain pytrees.

Every ``*_init`` returns a params dict; every ``*_apply`` is a pure function.
Stacked-layer params carry a leading ``L`` dim (scanned or indexed).
Compute dtype = cfg.dtype (bf16 target), params = cfg.param_dtype (f32),
f32 accumulation in every matmul that matters.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import partition as P_

Params = dict


def key_for(key: jax.Array, *path) -> jax.Array:
    for p in path:
        key = jax.random.fold_in(key, hash(str(p)) & 0x7FFFFFFF)
    return key


def _init_dense(key, shape, dtype, scale_axis: int = 0):
    fan_in = shape[scale_axis]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / norm / embedding
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32) -> Params:
    p = {"w": _init_dense(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array, compute_dtype) -> jax.Array:
    y = jax.lax.dot_general(
        x.astype(compute_dtype), p["w"].astype(compute_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(compute_dtype)


def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return ops.rmsnorm(x, p["scale"], eps=eps)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def embedding_init(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": _init_dense(key, (vocab, d), dtype, scale_axis=1)}


def embed(p: Params, tokens: jax.Array, compute_dtype) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0).astype(compute_dtype)


def unembed(p: Params, x: jax.Array, compute_dtype) -> jax.Array:
    """Logits in f32 (softmax stability)."""
    return jax.lax.dot_general(
        x.astype(compute_dtype), p["table"].astype(compute_dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32. Rotates the first
    ``fraction`` of head dims (GLM partial rotary)."""
    B, S, H, D = x.shape
    rot = int(D * fraction) // 2 * 2
    if rot == 0 or theta <= 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = jnp.power(theta, -jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA; full / sliding / chunked; optional KV cache)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": linear_init(key_for(key, "wq"), d, H * hd, bias=cfg.qkv_bias, dtype=dt),
        "wk": linear_init(key_for(key, "wk"), d, Hkv * hd, bias=cfg.qkv_bias, dtype=dt),
        "wv": linear_init(key_for(key, "wv"), d, Hkv * hd, bias=cfg.qkv_bias, dtype=dt),
        "wo": linear_init(key_for(key, "wo"), H * hd, d, dtype=dt),
    }
    if cfg.qk_norm and not cross:
        p["qnorm"] = rmsnorm_init(hd, dt)
        p["knorm"] = rmsnorm_init(hd, dt)
    return p


def layer_attn_pattern(cfg: ModelConfig, layer_idx: int) -> tuple[str, int]:
    """(pattern, span) for a layer: 'full' | ('sliding', w) | ('chunked', c)."""
    if cfg.attention == "sliding" and cfg.window:
        return "sliding", cfg.window
    if cfg.attention == "chunked" and cfg.attn_chunk:
        k = cfg.global_attn_every
        if k and (layer_idx + 1) % k == 0:
            return "full", 0       # iRoPE: every k-th layer is global
        return "chunked", cfg.attn_chunk
    return "full", 0


def attention_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                      # (B, S, d)
    positions: jax.Array,              # (B, S)
    *,
    pattern: str = "full",
    span: int = 0,
    causal: bool = True,
    kv_x: jax.Array | None = None,     # cross-attention source
    kv_positions: jax.Array | None = None,
    cache: dict | None = None,         # decode: {"k","v","pos","idx"}
    use_rope: bool = True,
) -> tuple[jax.Array, dict | None]:
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype

    q = linear(p["wq"], x, cdt).reshape(B, S, H, hd)
    src = x if kv_x is None else kv_x
    Skv = src.shape[1]
    k = linear(p["wk"], src, cdt).reshape(B, Skv, Hkv, hd)
    v = linear(p["wv"], src, cdt).reshape(B, Skv, Hkv, hd)

    if "qnorm" in p:
        q = rmsnorm(p["qnorm"], q)
        k = rmsnorm(p["knorm"], k)
    if use_rope and cfg.rope_theta > 0:
        q = apply_rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
        kpos = positions if kv_positions is None else kv_positions
        k = apply_rope(k, kpos, theta=cfg.rope_theta, fraction=cfg.rope_fraction)

    if cache is not None:
        out, cache = _cached_attention(cfg, q, k, v, positions, cache,
                                       pattern=pattern, span=span)
    else:
        window = span if pattern == "sliding" else None
        chunk = span if pattern == "chunked" else None
        out = ops.attention(q, k, v, causal=causal and kv_x is None,
                            window=window, chunk=chunk,
                            scale=cfg.attn_scale or None,
                            q_chunk=cfg.attn_q_chunk)
    out = out.reshape(B, S, H * hd)
    return linear(p["wo"], out, cdt), cache


def cache_len_for(cfg: ModelConfig, layer_idx: int, max_len: int) -> int:
    pattern, span = layer_attn_pattern(cfg, layer_idx)
    if pattern in ("sliding", "chunked") and span:
        return min(max_len, span)
    return max_len


def init_attn_cache(cfg: ModelConfig, layer_idx: int, batch: int,
                    max_len: int) -> dict:
    L = cache_len_for(cfg, layer_idx, max_len)
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    return {
        "k": jnp.zeros((batch, L, Hkv, hd), cdt),
        "v": jnp.zeros((batch, L, Hkv, hd), cdt),
        "pos": jnp.full((batch, L), -1, jnp.int32),   # absolute pos per slot
    }


def _cached_attention(cfg, q, k_new, v_new, positions, cache, *,
                      pattern: str, span: int):
    """Decode/step attention against a (ring-buffered) KV cache.

    Slots are addressed ``pos % cache_len`` — a ring buffer for sliding/
    chunked layers (cache_len == span), plain indexed for full layers.
    Keys are cached post-RoPE; masking uses per-slot absolute positions.
    """
    B, S, Hkv, hd = k_new.shape
    L = cache["k"].shape[1]
    slots = positions % L                                   # (B, S)
    bidx = jnp.arange(B)[:, None]
    ck = cache["k"].at[bidx, slots].set(k_new)
    cv = cache["v"].at[bidx, slots].set(v_new)
    cpos = cache["pos"].at[bidx, slots].set(positions)
    new_cache = {"k": ck, "v": cv, "pos": cpos}

    group = cfg.num_heads // Hkv
    qg = q.reshape(q.shape[0], q.shape[1], Hkv, group, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   ck.astype(jnp.float32)) * cfg.attention_scale
    qpos = positions[:, :, None]                            # (B, S, 1)
    kpos = cpos[:, None, :]                                 # (B, 1, L)
    mask = (kpos >= 0) & (kpos <= qpos)                     # filled & causal
    if pattern == "sliding" and span:
        mask &= (qpos - kpos) < span
    if pattern == "chunked" and span:
        mask &= (qpos // span) == (kpos // span)
    s = jnp.where(mask[:, None, None], s, -1e30)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1),
                     cv.astype(jnp.float32)).astype(q.dtype)
    out = out.reshape(q.shape)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (swiglu / gelu / relu^2)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    p = {"up": linear_init(key_for(key, "up"), d, f, dtype=dt),
         "down": linear_init(key_for(key, "down"), f, d, dtype=dt)}
    if cfg.mlp == "swiglu":
        p["gate"] = linear_init(key_for(key, "gate"), d, f, dtype=dt)
    return p


def mlp_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    cdt = cfg.compute_dtype
    up = linear(p["up"], x, cdt)
    if cfg.mlp == "swiglu":
        act = jax.nn.silu(linear(p["gate"], x, cdt).astype(jnp.float32))
        h = (act * up.astype(jnp.float32)).astype(cdt)
    elif cfg.mlp == "gelu":
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(cdt)
    else:  # relu2 (Nemotron)
        r = jnp.maximum(up.astype(jnp.float32), 0.0)
        h = (r * r).astype(cdt)
    h = P_.constrain(h, ("batch", None, "ff"))
    return linear(p["down"], h, cdt)
