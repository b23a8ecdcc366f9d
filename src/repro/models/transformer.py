"""Blocks and layer stacks for all assigned families.

One homogeneous ``block_init``/``block_apply`` per architecture family:
  dense/vlm : pre-norm GQA attention + pre-norm MLP
  moe       : pre-norm GQA attention + pre-norm MoE
  ssm       : pre-norm Mamba-2 mixer (no MLP — pure Mamba-2 stack)
  hybrid    : pre-norm (attention ∥ SSM heads, fused) + pre-norm MLP (Hymba)
  encdec    : whisper encoder blocks (bidir) + decoder blocks w/ cross-attn

A config with ``layer_types`` (Granite 4.0-H) interleaves two block kinds
instead: ``"mamba"`` (pre-norm Mamba-2 mixer) and ``"attention"`` (pre-norm
GQA), each followed by the pre-norm MoE/MLP. Its layers are a list of
per-layer param dicts, each with only its own mixer's weights, and are
never sliced out of a stack (a kernel's operand sliced from a stack is a
copy of the layer's weights on every call).

Train/prefill run the sequence path; decode runs the single-step path
against per-layer caches. Layer params are stacked (leading L dim):
``lax.scan`` over layers for training (grouped by ``global_attn_every``
to keep heterogeneous attention patterns static), unrolled indexing for
decode.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import partition as P_
from . import layers as L
from . import moe as M
from . import ssm as S

Params = dict


def _norm_init(cfg: ModelConfig, d: int):
    return (L.layernorm_init(d, jnp.dtype(cfg.param_dtype))
            if cfg.family == "encdec"
            else L.rmsnorm_init(d, jnp.dtype(cfg.param_dtype)))


def _norm(cfg: ModelConfig, p, x):
    return (L.layernorm(p, x) if cfg.family == "encdec"
            else L.rmsnorm(p, x, eps=cfg.norm_eps))


# ---------------------------------------------------------------------------
# Decoder block (all families)
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, kind: str | None = None) -> Params:
    d = cfg.d_model
    p: Params = {"norm1": _norm_init(cfg, d)}
    if cfg.family == "ssm":
        p["ssm"] = S.ssm_init(L.key_for(key, "ssm"), cfg)
        return p
    if kind == "mamba":
        p["ssm"] = S.ssm_init(L.key_for(key, "ssm"), cfg)
    else:
        p["attn"] = L.attention_init(L.key_for(key, "attn"), cfg)
    if cfg.hybrid_ssm:
        p["ssm"] = S.ssm_init(L.key_for(key, "ssm"), cfg)
        p["attn_out_norm"] = L.rmsnorm_init(d, jnp.dtype(cfg.param_dtype))
        p["ssm_out_norm"] = L.rmsnorm_init(d, jnp.dtype(cfg.param_dtype))
    p["norm2"] = _norm_init(cfg, d)
    if cfg.num_experts:
        p["moe"] = M.moe_init(L.key_for(key, "moe"), cfg)
    else:
        p["mlp"] = L.mlp_init(L.key_for(key, "mlp"), cfg)
    if cfg.family == "encdec":
        p["cross_norm"] = _norm_init(cfg, d)
        p["cross"] = L.attention_init(L.key_for(key, "cross"), cfg, cross=True)
    return p


def block_apply(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    layer_idx: int,
    mode: str = "train",                 # train | prefill | decode
    cache: dict | None = None,
    enc_out: jax.Array | None = None,
    kind: str | None = None,             # interleaved stacks: the mixer
) -> tuple[jax.Array, jax.Array, dict | None]:
    aux = jnp.zeros((), jnp.float32)
    rs = cfg.residual_scale
    new_cache: dict | None = dict(cache) if cache is not None else None

    h = _norm(cfg, p["norm1"], x)
    if cfg.family == "ssm" or kind == "mamba":
        with jax.named_scope("mamba2"):
            y, st = S.ssm_apply(p["ssm"], cfg, h,
                                state=cache["ssm"] if cache else None)
        if new_cache is not None:
            new_cache["ssm"] = st
        x = x + rs * y
        if cfg.family == "ssm":
            return x, aux, new_cache
    else:
        x = _attention_mixer(p, cfg, x, h, positions, layer_idx=layer_idx,
                             mode=mode, cache=cache, new_cache=new_cache,
                             enc_out=enc_out)
    # second half: pre-norm MoE or MLP on the residual
    h2 = _norm(cfg, p["norm2"], x)
    if cfg.num_experts:
        mlp_out, aux = M.moe_apply(p["moe"], cfg, h2)
    else:
        mlp_out = L.mlp_apply(p["mlp"], cfg, h2)
    return x + rs * mlp_out, aux, new_cache


def _attention_mixer(p, cfg, x, h, positions, *, layer_idx, mode, cache,
                     new_cache, enc_out):
    """Attention (with Hymba's parallel SSM heads, whisper's cross-attention)
    on the normed ``h``; returns the residual stream after it."""
    rs = cfg.residual_scale
    pattern, span = L.layer_attn_pattern(cfg, layer_idx)
    if mode == "decode":
        attn_out, ac = L.attention_apply(
            p["attn"], cfg, h, positions, pattern=pattern, span=span,
            cache=cache["attn"])
        new_cache["attn"] = ac
    else:
        attn_out, _ = L.attention_apply(
            p["attn"], cfg, h, positions, pattern=pattern, span=span)
        if mode == "prefill":
            new_cache["attn"] = _write_prefill_cache(
                cfg, p["attn"], h, positions, cache["attn"])

    if cfg.hybrid_ssm:
        ssm_out, st = S.ssm_apply(p["ssm"], cfg, h,
                                  state=cache["ssm"] if cache else None)
        if new_cache is not None and mode != "train":
            new_cache["ssm"] = st
        fused = 0.5 * (L.rmsnorm(p["attn_out_norm"], attn_out)
                       + L.rmsnorm(p["ssm_out_norm"], ssm_out))
        x = x + rs * fused
    else:
        x = x + rs * attn_out

    if cfg.family == "encdec" and (
            enc_out is not None
            or (cache is not None and "cross_kv" in cache)):
        hc = _norm(cfg, p["cross_norm"], x)
        if mode == "decode" and cache is not None and "cross_kv" in cache:
            c_out = _cross_from_cache(p["cross"], cfg, hc, cache["cross_kv"])
        else:
            c_out, _ = L.attention_apply(
                p["cross"], cfg, hc, positions, causal=False, kv_x=enc_out,
                kv_positions=jnp.zeros(enc_out.shape[:2], jnp.int32),
                use_rope=False)
            if new_cache is not None:
                new_cache["cross_kv"] = _make_cross_cache(p["cross"], cfg, enc_out)
        x = x + rs * c_out
    return x


def _write_prefill_cache(cfg, pa, h, positions, cache):
    """Recompute K/V for the tail of the sequence and fill the ring cache."""
    B, Sq, _ = h.shape
    Lc = cache["k"].shape[1]
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    k = L.linear(pa["wk"], h, cdt).reshape(B, Sq, Hkv, hd)
    v = L.linear(pa["wv"], h, cdt).reshape(B, Sq, Hkv, hd)
    if "knorm" in pa:
        k = L.rmsnorm(pa["knorm"], k)
    if cfg.rope_theta > 0:
        k = L.apply_rope(k, positions, theta=cfg.rope_theta,
                         fraction=cfg.rope_fraction)
    take = min(Sq, Lc)
    k, v, pos = k[:, -take:], v[:, -take:], positions[:, -take:]
    slots = pos % Lc
    bidx = jnp.arange(B)[:, None]
    return {"k": cache["k"].at[bidx, slots].set(k),
            "v": cache["v"].at[bidx, slots].set(v),
            "pos": cache["pos"].at[bidx, slots].set(pos)}


def _make_cross_cache(pa, cfg, enc_out):
    B, Se, _ = enc_out.shape
    Hkv, hd = cfg.num_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    return {"k": L.linear(pa["wk"], enc_out, cdt).reshape(B, Se, Hkv, hd),
            "v": L.linear(pa["wv"], enc_out, cdt).reshape(B, Se, Hkv, hd)}


def _cross_from_cache(pa, cfg, h, kv):
    B, Sq, _ = h.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    q = L.linear(pa["wq"], h, cdt).reshape(B, Sq, H, hd)
    group = H // Hkv
    kf = jnp.repeat(kv["k"], group, axis=2)
    vf = jnp.repeat(kv["v"], group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * (hd ** -0.5)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                     vf.astype(jnp.float32)).astype(cdt)
    return L.linear(pa["wo"], out.reshape(B, Sq, H * hd), cdt)


# ---------------------------------------------------------------------------
# Encoder block (whisper; bidirectional, no rope)
# ---------------------------------------------------------------------------

def encoder_block_init(key, cfg: ModelConfig) -> Params:
    return {
        "norm1": _norm_init(cfg, cfg.d_model),
        "attn": L.attention_init(L.key_for(key, "attn"), cfg),
        "norm2": _norm_init(cfg, cfg.d_model),
        "mlp": L.mlp_init(L.key_for(key, "mlp"), cfg),
    }


def encoder_block_apply(p, cfg, x, positions):
    h = _norm(cfg, p["norm1"], x)
    a, _ = L.attention_apply(p["attn"], cfg, h, positions, causal=False,
                             use_rope=False)
    x = x + a
    x = x + L.mlp_apply(p["mlp"], cfg, _norm(cfg, p["norm2"], x))
    return x


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def stack_init(key, cfg: ModelConfig, n_layers: int, init_fn) -> Params:
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_fn(k, cfg))(keys)


def layers_init(key, cfg: ModelConfig) -> Params | list:
    """The decoder's layer params: one stack, or a list with one block per
    layer of an interleaved config."""
    if not cfg.layer_types:
        return stack_init(key, cfg, cfg.num_layers, block_init)
    return [block_init(k, cfg, kind=kind) for k, kind in zip(
        jax.random.split(key, cfg.num_layers), cfg.layer_types)]


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def decoder_stack(params_layers: Params, cfg: ModelConfig, x: jax.Array,
                  positions: jax.Array, *, mode: str = "train",
                  caches: list | None = None, enc_out: jax.Array | None = None):
    """Run all decoder blocks. Returns (x, total_aux, new_caches)."""
    n = cfg.num_layers
    if (cfg.scan_layers and caches is None and cfg.family != "encdec"
            and not cfg.layer_types):
        g = cfg.global_attn_every if cfg.global_attn_every else 1
        assert n % g == 0

        def group_body(carry, lp):
            xx, aux = carry
            for j in range(g):
                pj = jax.tree_util.tree_map(lambda a: a[j], lp) if g > 1 else lp
                xx, a, _ = block_apply(pj, cfg, xx, positions,
                                       layer_idx=j, mode=mode)
                aux = aux + a
            xx = P_.constrain(xx, ("batch", None, None))
            return (xx, aux), None

        body = _remat_wrap(cfg, group_body)
        stacked = params_layers
        if g > 1:
            stacked = jax.tree_util.tree_map(
                lambda a: a.reshape((n // g, g) + a.shape[1:]), stacked)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   stacked)
        return x, aux, None

    # unrolled (decode / prefill / encdec / smoke)
    aux = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for i in range(n):
        kind = cfg.layer_types[i] if cfg.layer_types else None
        pi = (params_layers[i] if kind else
              jax.tree_util.tree_map(lambda a: a[i], params_layers))
        ci = caches[i] if caches is not None else None

        def run_block(pi_, x_, pos_, ci_, enc_, _i=i, _kind=kind):
            return block_apply(pi_, cfg, x_, pos_, layer_idx=_i, mode=mode,
                               cache=ci_, enc_out=enc_, kind=_kind)

        if cfg.remat != "none" and mode == "train":
            if cfg.remat == "dots":
                run_block = jax.checkpoint(
                    run_block,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            else:
                run_block = jax.checkpoint(run_block)
        x, a, nc = run_block(pi, x, positions, ci, enc_out)
        aux = aux + a
        if new_caches is not None:
            new_caches.append(nc)
    return x, aux, new_caches


def encoder_stack(params_layers: Params, cfg: ModelConfig, x: jax.Array):
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
    if cfg.scan_layers:
        def body(xx, lp):
            xx = encoder_block_apply(lp, cfg, xx, positions)
            return xx, None
        x, _ = jax.lax.scan(_remat_wrap(cfg, body) if cfg.remat != "none"
                            else body, x, params_layers)
        return x
    for i in range(cfg.encoder_layers):
        pi = jax.tree_util.tree_map(lambda a: a[i], params_layers)
        x = encoder_block_apply(pi, cfg, x, positions)
    return x
