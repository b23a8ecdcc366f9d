"""Mamba-2 mixer (SSD) — sequence path via the chunked-SSD kernel, decode
path via the O(1) single-step recurrence on a carried state.

Layout: in_proj -> [z | xBC | dt]; causal conv over xBC; SSD over heads;
gated RMSNorm; out_proj (follows the Mamba-2 reference architecture).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import partition as P_
from . import layers as L

Params = dict


def ssm_dims(cfg: ModelConfig) -> dict:
    di = cfg.ssm_inner
    H = cfg.ssm_heads
    G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    conv_ch = di + 2 * G * N
    return dict(d_inner=di, heads=H, P=cfg.ssm_headdim, groups=G, N=N,
                K=K, conv_ch=conv_ch,
                in_dim=2 * di + 2 * G * N + H)


def ssm_init(key, cfg: ModelConfig) -> Params:
    dm = cfg.d_model
    dd = ssm_dims(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    H = dd["heads"]
    p = {
        "A_log": jnp.zeros((H,), dt),                 # A = -exp(A_log) = -1
        "D": jnp.ones((H,), dt),
        "dt_bias": jnp.full((H,), -2.0, dt),          # softplus(-2) ~ 0.12
        "norm": L.rmsnorm_init(dd["d_inner"], dt),
        "out_proj": {"w": L._init_dense(L.key_for(key, "out"), (dd["d_inner"], dm), dt)},
    }
    if cfg.ssm_split_proj:
        # shard-boundary-aligned layout (§Perf iteration 3): z/x projections
        # TP-sharded on their own, B/C/dt small and replicated; depthwise
        # conv splits likewise (mathematically identical to the fused conv).
        gn = dd["groups"] * dd["N"]
        di = dd["d_inner"]
        p.update({
            "z_proj": {"w": L._init_dense(L.key_for(key, "z"), (dm, di), dt)},
            "x_proj": {"w": L._init_dense(L.key_for(key, "x"), (dm, di), dt)},
            "b_proj": {"w": L._init_dense(L.key_for(key, "b"), (dm, gn), dt)},
            "c_proj": {"w": L._init_dense(L.key_for(key, "c"), (dm, gn), dt)},
            "dt_proj": {"w": L._init_dense(L.key_for(key, "dt"), (dm, H), dt)},
            "xconv": {"w": L._init_dense(L.key_for(key, "xc"), (dd["K"], di), dt),
                      "b": jnp.zeros((di,), dt)},
            "bconv": {"w": L._init_dense(L.key_for(key, "bc"), (dd["K"], gn), dt),
                      "b": jnp.zeros((gn,), dt)},
            "cconv": {"w": L._init_dense(L.key_for(key, "cc"), (dd["K"], gn), dt),
                      "b": jnp.zeros((gn,), dt)},
        })
    else:
        p.update({
            "in_proj": {"w": L._init_dense(L.key_for(key, "in"),
                                           (dm, dd["in_dim"]), dt)},
            "conv": {"w": L._init_dense(L.key_for(key, "conv"),
                                        (dd["K"], dd["conv_ch"]), dt),
                     "b": jnp.zeros((dd["conv_ch"],), dt)},
        })
    return p


def _split_in(cfg: ModelConfig, proj: jax.Array):
    dd = ssm_dims(cfg)
    di, gn = dd["d_inner"], dd["groups"] * dd["N"]
    z = proj[..., :di]
    xBC = proj[..., di:di + di + 2 * gn]
    dt = proj[..., di + di + 2 * gn:]
    return z, xBC, dt


def _causal_conv(w: jax.Array, b: jax.Array, xBC: jax.Array,
                 state: jax.Array | None):
    """Depthwise causal conv1d, kernel K. state: (B, K-1, C) history."""
    K, C = w.shape
    Bz, S, _ = xBC.shape
    if state is None:
        hist = jnp.zeros((Bz, K - 1, C), xBC.dtype)
    else:
        hist = state.astype(xBC.dtype)
    full = jnp.concatenate([hist, xBC], axis=1)           # (B, S+K-1, C)
    out = jnp.zeros((Bz, S, C), jnp.float32)
    for k in range(K):
        out = out + full[:, k:k + S].astype(jnp.float32) * w[k].astype(jnp.float32)
    out = out + b.astype(jnp.float32)
    new_state = full[:, -(K - 1):] if K > 1 else jnp.zeros((Bz, 0, C), xBC.dtype)
    return jax.nn.silu(out).astype(xBC.dtype), new_state


def ssm_apply(p: Params, cfg: ModelConfig, x: jax.Array,
              state: dict | None = None) -> tuple[jax.Array, dict | None]:
    """x: (B, S, d_model). state (decode): {"conv": (B,K-1,C), "ssd": (B,H,P,N)}."""
    Bz, S, _ = x.shape
    dd = ssm_dims(cfg)
    H, Pd, G, N = dd["heads"], dd["P"], dd["groups"], dd["N"]
    cdt = cfg.compute_dtype

    if cfg.ssm_split_proj:
        z = L.linear(p["z_proj"], x, cdt)
        xr = L.linear(p["x_proj"], x, cdt)
        br = L.linear(p["b_proj"], x, cdt)
        cr = L.linear(p["c_proj"], x, cdt)
        dt_raw = L.linear(p["dt_proj"], x, cdt)
        cs = state["conv"] if state is not None else None
        di, gn = dd["d_inner"], G * N
        xcs = cs[..., :di] if cs is not None else None
        bcs = cs[..., di:di + gn] if cs is not None else None
        ccs = cs[..., di + gn:] if cs is not None else None
        xr, ncx = _causal_conv(p["xconv"]["w"], p["xconv"]["b"], xr, xcs)
        br, ncb = _causal_conv(p["bconv"]["w"], p["bconv"]["b"], br, bcs)
        cr, ncc = _causal_conv(p["cconv"]["w"], p["cconv"]["b"], cr, ccs)
        new_conv = jnp.concatenate([ncx, ncb, ncc], axis=-1)
        xin = xr.reshape(Bz, S, H, Pd)
        Bm = br.reshape(Bz, S, G, N)
        Cm = cr.reshape(Bz, S, G, N)
    else:
        proj = L.linear(p["in_proj"], x, cdt)
        proj = P_.constrain(proj, ("batch", None, "ssm_inner"))
        z, xBC, dt_raw = _split_in(cfg, proj)

        conv_state = state["conv"] if state is not None else None
        xBC, new_conv = _causal_conv(p["conv"]["w"], p["conv"]["b"], xBC,
                                     conv_state)

        xin = xBC[..., :dd["d_inner"]].reshape(Bz, S, H, Pd)
        Bm = xBC[..., dd["d_inner"]:dd["d_inner"] + G * N].reshape(Bz, S, G, N)
        Cm = xBC[..., dd["d_inner"] + G * N:].reshape(Bz, S, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))        # (B,S,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    init = state["ssd"] if state is not None else None
    if S == 1 and state is not None:
        # decode: single-step recurrence, no scan
        dA = jnp.exp(dt[:, 0, :] * A[None, :])                      # (B,H)
        Brep = jnp.repeat(Bm[:, 0], H // G, axis=1).astype(jnp.float32)  # (B,H,N)
        dBx = jnp.einsum("bh,bhn,bhp->bhpn", dt[:, 0], Brep,
                         xin[:, 0].astype(jnp.float32))
        h = dA[:, :, None, None] * init.astype(jnp.float32) + dBx
        Crep = jnp.repeat(Cm[:, 0], H // G, axis=1).astype(jnp.float32)  # (B,H,N)
        y = jnp.einsum("bhpn,bhn->bhp", h, Crep)
        y = y + p["D"].astype(jnp.float32)[None, :, None] * xin[:, 0].astype(jnp.float32)
        y = y.reshape(Bz, 1, dd["d_inner"]).astype(cdt)
        new_ssd = h
    else:
        with jax.named_scope("mamba2.ssd"):
            y, new_ssd = ops.ssd(xin, dt.astype(cdt), A,
                                 Bm.astype(cdt), Cm.astype(cdt),
                                 D=p["D"].astype(jnp.float32),
                                 init_state=init, chunk=cfg.ssm_chunk)
        y = y.reshape(Bz, S, dd["d_inner"]).astype(cdt)

    # gated norm + out projection
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = L.rmsnorm(p["norm"], y.astype(cdt), eps=cfg.norm_eps)
    out = L.linear(p["out_proj"], y, cdt)
    new_state = ({"conv": new_conv, "ssd": new_ssd.astype(jnp.float32)}
                 if state is not None else None)
    return out, new_state


def init_ssm_state(cfg: ModelConfig, batch: int) -> dict:
    dd = ssm_dims(cfg)
    return {
        "conv": jnp.zeros((batch, dd["K"] - 1, dd["conv_ch"]), cfg.compute_dtype),
        "ssd": jnp.zeros((batch, dd["heads"], dd["P"], dd["N"]), jnp.float32),
    }
