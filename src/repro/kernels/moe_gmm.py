"""Grouped (per-expert) matmul — Pallas TPU kernel for MoE expert GEMMs.

Capacity-based MoE dispatch produces dense per-expert activations
``x: (E, C, d)`` multiplied by per-expert weights ``w: (E, d, f)``.
The kernel grids over (expert, C-tiles, f-tiles, d-tiles) with a VMEM f32
accumulator; (bc, bd, bf) default to MXU-aligned 128 tiles. The expert
dimension is embarrassingly parallel — on an EP-sharded mesh each device
runs only its local experts (the round-robin root-task distribution of the
paper, realized as a static shard).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref):
    kd = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(kd == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                      # (bc, bd)
    w = w_ref[0]                      # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _divisor_block(dim: int, pref: int, lanes: int = 128) -> int:
    """Largest lane multiple <= ``pref`` that divides ``dim``, else ``dim``.

    A block must tile the operand exactly; a whole dimension is always a
    legal block when no lane multiple divides it.
    """
    for b in range(min(pref, dim) // lanes * lanes, 0, -lanes):
        if dim % b == 0:
            return b
    return dim


def grouped_matmul(
    x: jax.Array,   # (E, C, d)
    w: jax.Array,   # (E, d, f)
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    E, C, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, max(8, 1 << (C - 1).bit_length()))
    block_d = _divisor_block(d, block_d)
    block_f = _divisor_block(f, block_f)
    c_pad = math.ceil(C / block_c) * block_c
    if c_pad != C:
        x = jnp.pad(x, ((0, 0), (0, c_pad - C), (0, 0)))

    grid = (E, c_pad // block_c, f // block_f, d // block_d)
    out = compat.pallas_call(
        _gmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, c_pad, f), x.dtype),
        scratch_shapes=[compat.vmem((block_c, block_f), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(x, w)
    return out[:, :C]
