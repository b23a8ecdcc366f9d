"""Mamba-2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

The SSD decomposition splits the sequential SSM recurrence into
  (1) an *intra-chunk* part — dense, attention-like matmuls of size
      (Q x N) @ (N x Q) and (Q x Q) @ (Q x P) per chunk: MXU work, and
  (2) an *inter-chunk* state recurrence over n_chunks steps — tiny,
      sequential, O(S/Q) depth.

Part (1) dominates FLOPs and is the Pallas kernel below, gridded over
(batch*heads, chunks) with everything for one chunk resident in VMEM
(Q=chunk, N=state, P=headdim all 64/128-aligned → MXU-shaped matmuls).
Part (2) plus the cross-chunk output correction stay in jnp (a
``lax.scan`` over n_chunks elements and one small einsum) — they are
bandwidth-trivial and XLA fuses them well.

Validated against ``ref.ssd_ref`` (exact sequential oracle) and
``ref.ssd_chunked_ref`` (blockwise jnp twin of this kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _ssd_chunk_kernel(xs_ref, b_ref, c_ref, lda_ref,
                      y_ref, state_ref, cdecay_ref):
    """One (batch*head, chunk) cell: intra-chunk output + local end-state.

    xs  : (Q, P)  dt * x
    b,c : (Q, N)  the head's group's B and C
    lda : (1, Q)  log dA = dt * A, as a row
    out y      : (Q, P)   intra-chunk contribution
    out state  : (N, P)   chunk end-state (before inter-chunk recurrence)
    out cdecay : (1, 1)   total log-decay across the chunk
    """
    xs = xs_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    c = c_ref[0].astype(jnp.float32)
    lda = lda_ref[0].astype(jnp.float32)          # (1, Q)
    Q = xs.shape[0]

    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # Mosaic has no cumsum: the inclusive prefix sum is a masked reduction
    # in f32 (exact adds, unlike a bf16-pass MXU matmul), taken once as a
    # column and moved to a row through the diagonal (no relayout needed).
    lda_b = jnp.broadcast_to(lda, (Q, Q))         # [i, j] = lda[j]
    cums = jnp.sum(jnp.where(lj <= li, lda_b, 0.0), axis=1,
                   keepdims=True)                 # (Q, 1) inclusive
    cums_row = jnp.sum(jnp.where(li == lj, cums, 0.0), axis=0,
                       keepdims=True)             # (1, Q) same values
    # decay(i<-j) = exp(cums[i] - cums[j]) for j <= i
    diff = cums - cums_row                        # (Q_i, Q_j)
    L = jnp.where(li >= lj, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(scores * L, xs, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q, P)
    y_ref[0] = y.astype(y_ref.dtype)

    total = cums[Q - 1:Q, :]                      # (1, 1)
    decay_to_end = jnp.exp(total - cums)          # (Q, 1)
    state = jax.lax.dot_general(b * decay_to_end, xs,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (N, P)
    state_ref[0] = state.astype(state_ref.dtype)
    cdecay_ref[0] = total.astype(cdecay_ref.dtype)


def ssd_intra_chunk(xs, b, c, lda, *, chunk: int, heads_per_group: int = 1,
                    interpret: bool = False):
    """Pallas-gridded intra-chunk pass.

    xs: (BH, S, P); b, c: (BG, S, N), one row per (batch, group), shared by
    the ``heads_per_group`` consecutive heads of the group; lda:
    (BH * nc, 1, chunk), each chunk's log-decays as a row. S % chunk == 0.
    Returns (y_intra (BH,S,P), state_local (BH,nc,N,P), cdecay (BH,nc,1,1)).
    """
    BH, S, P = xs.shape
    N = b.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rep = heads_per_group

    grid = (BH, nc)
    seq_map = lambda h, c_: (h, c_, 0)
    group_map = lambda h, c_: (h // rep, c_, 0)
    cell_map = lambda h, c_: (h * nc + c_, 0, 0)
    out = compat.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), seq_map),
            pl.BlockSpec((1, chunk, N), group_map),
            pl.BlockSpec((1, chunk, N), group_map),
            pl.BlockSpec((1, 1, chunk), cell_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), seq_map),
            pl.BlockSpec((1, N, P), cell_map),
            pl.BlockSpec((1, 1, 1), cell_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH * nc, N, P), jnp.float32),
            jax.ShapeDtypeStruct((BH * nc, 1, 1), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_intra_chunk",
    )(xs, b, c, lda)
    y_intra, state_local, cdecay = out
    return (y_intra,
            state_local.reshape(BH, nc, N, P),
            cdecay.reshape(BH, nc, 1, 1))


def ssd(
    x: jax.Array,     # (B, S, H, P)
    dt: jax.Array,    # (B, S, H)
    A: jax.Array,     # (H,)
    Bm: jax.Array,    # (B, S, G, N)
    Cm: jax.Array,    # (B, S, G, N)
    D: jax.Array | None = None,
    init_state: jax.Array | None = None,   # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full SSD: Pallas intra-chunk + jnp inter-chunk. Matches ``ref.ssd_ref``."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    # layout: (B, S, H, P) -> (B*H, S, P); B and C stay one row per
    # (batch, group), read by each of the group's heads in the kernel
    dtf = dt.astype(jnp.float32)
    xs = jnp.moveaxis(x.astype(jnp.float32) * dtf[..., None], 2, 1).reshape(
        Bsz * H, S, P)
    Bg = jnp.moveaxis(Bm.astype(jnp.float32), 2, 1).reshape(Bsz * G, S, N)
    Cg = jnp.moveaxis(Cm.astype(jnp.float32), 2, 1).reshape(Bsz * G, S, N)
    lda = jnp.moveaxis(dtf * A[None, None, :], 2, 1)          # (B, H, S)

    y_intra, state_local, cdecay = ssd_intra_chunk(
        xs, Bg, Cg, lda.reshape(Bsz * H * nc, 1, chunk), chunk=chunk,
        heads_per_group=rep, interpret=interpret)

    # inter-chunk recurrence (tiny: nc sequential steps over (BH, N, P))
    h0 = (jnp.zeros((Bsz * H, N, P), jnp.float32) if init_state is None
          else jnp.swapaxes(init_state.astype(jnp.float32), 2, 3)
          .reshape(Bsz * H, N, P))
    cd = jnp.exp(cdecay[..., 0, 0])                     # (BH, nc)

    def step(h, inp):
        cd_c, sl_c = inp                                # (BH,), (BH, N, P)
        h_prev = h
        h = cd_c[:, None, None] * h + sl_c
        return h, h_prev

    hT, h_prevs = jax.lax.scan(
        step, h0, (jnp.moveaxis(cd, 1, 0), jnp.moveaxis(state_local, 1, 0)))
    h_prev = h_prevs.reshape(nc, Bsz, G, rep, N, P)

    # cross-chunk output: y[i] += exp(cums[i]) * C[i] @ h_prev(chunk(i))
    cums = jnp.cumsum(lda.reshape(Bsz, G, rep, nc, chunk), axis=-1)
    c_c = Cg.reshape(Bsz, G, nc, chunk, N)
    y_inter = jnp.einsum("bgcin,cbgrnp,bgrci->bgrcip", c_c, h_prev,
                         jnp.exp(cums))
    y = y_intra + y_inter.reshape(Bsz * H, S, P)

    y = jnp.moveaxis(y.reshape(Bsz, H, S, P), 1, 2)     # (B, S, H, P)
    if D is not None:
        y = y + D[None, None, :, None] * x.astype(jnp.float32)
    hT = jnp.swapaxes(hT.reshape(Bsz, H, N, P), 2, 3)   # (B, H, P, N)
    return y.astype(x.dtype), hT
