"""Fused RMSNorm (+ optional residual add) — Pallas TPU kernel.

One VMEM round-trip instead of three (add, norm, scale): rows are tiled
(block_rows, d) with d resident, matching the (8k..) token-major layouts of
the model stack. Hot in every block (2 norms/layer), bandwidth-bound.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_res_kernel(x_ref, r_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


#: Elements of one block: its f32 temporaries must fit the 16 MiB of
#: scoped VMEM (256 rows of 4096, or 128 of a Mamba-2 d_inner of 8192).
BLOCK_ELEMENTS = 1 << 20


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            residual: jax.Array | None = None, *, block_rows: int = 256,
            interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    block_rows = min(block_rows, max(8, BLOCK_ELEMENTS // d // 8 * 8),
                     max(8, 1 << (n - 1).bit_length()))
    n_pad = math.ceil(n / block_rows) * block_rows
    pad = n_pad - n
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    row_map = lambda i: (i, 0)
    w_map = lambda i: (0, 0)
    common = dict(
        grid=(n_pad // block_rows,),
        out_specs=pl.BlockSpec((block_rows, d), row_map),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="rmsnorm",
    )
    if residual is None:
        out = compat.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps),
            in_specs=[pl.BlockSpec((block_rows, d), row_map),
                      pl.BlockSpec((1, d), w_map)],
            **common,
        )(xf, w.reshape(1, d))
    else:
        rf = residual.reshape(-1, d)
        if pad:
            rf = jnp.pad(rf, ((0, pad), (0, 0)))
        out = compat.pallas_call(
            functools.partial(_rmsnorm_res_kernel, eps=eps),
            in_specs=[pl.BlockSpec((block_rows, d), row_map),
                      pl.BlockSpec((block_rows, d), row_map),
                      pl.BlockSpec((1, d), w_map)],
            **common,
        )(xf, rf, w.reshape(1, d))
    return out[:n].reshape(orig_shape)
