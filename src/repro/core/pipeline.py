"""Pipeline parallelism lowered from the pipeline TDG.

``schedule.pipeline_tdg`` / ``one_f_one_b_order`` define the *logical*
schedule (the static taskgraph). This module executes it on a mesh axis:
a GPipe-style rotation where stage s holds its layer shard and microbatches
flow s -> s+1 via ``ppermute`` (the TPU-native edge: a collective-permute
per TDG activation edge). The wave structure of the shard_map loop is
exactly ``topo_waves(pipeline_tdg(S, M, include_backward=False))`` —
asserted by tests, which is the point: the paper's "schedule once, replay"
applied to pipeline orchestration.

Backward is obtained by differentiating through the rotation (ppermute
transposes to the reverse permute), which reproduces the reverse schedule
without hand-writing it.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,            # (stage_params, x) -> y  (one stage)
    stage_params,                  # pytree, leaves stacked on leading S dim
    x_microbatches: jax.Array,     # (M, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Forward pipeline: returns (M, mb, ...) outputs of the LAST stage.

    Steady-state utilization M/(M+S-1) — the classic GPipe bubble; the
    1F1B variant reorders backward into the bubble (see
    ``schedule.one_f_one_b_order``), with identical wave count.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    T = M + S - 1                   # total waves (pipeline TDG depth)

    def per_stage(params, xs):
        # params sliced per stage (leading block dim 1); xs replicated (full)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)   # rotating activation
        outs = jnp.zeros_like(xs)

        def wave(t, state):
            carry, outs = state
            # stage 0 injects microbatch t; others take the rotated carry
            mb_idx = jnp.clip(t, 0, M - 1)
            my_in = jnp.where(sid == 0, xs[mb_idx], carry)
            active = (t - sid >= 0) & (t - sid < M)
            y = stage_fn(params, my_in)
            y = jnp.where(active, y, carry)
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            record = active & (sid == S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, y, outs[out_idx]), out_idx, 0)
            # rotate activations to the next stage
            carry = jax.lax.ppermute(
                y, axis, perm=[(i, (i + 1) % S) for i in range(S)])
            return carry, outs

        _, outs = jax.lax.fori_loop(0, T, wave, (carry_in, outs))
        # only stage S-1 holds real outputs; broadcast via masked psum
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs[None]

    spec_p = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_p, P(None)),   # microbatches replicated
                       out_specs=P(axis),
                       check_vma=False)
    # feed every stage the full microbatch tensor; stage 0 uses it
    outs = fn(stage_params, x_microbatches)    # (S, M, mb, ...) stacked
    return outs[0]                             # identical post-broadcast


def pipeline_waves(n_stages: int, n_microbatches: int) -> int:
    """Forward wave count = TDG depth (checked against topo_waves in tests)."""
    return n_microbatches + n_stages - 1


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
