"""Mixture-of-Experts layer: top-k router + capacity-based dispatch +
grouped expert GEMMs (Pallas kernel on TPU), EP-shardable over "experts".

Dispatch is static-shape (capacity factor) so the whole MoE layer is a
fixed wave of per-expert tasks in the TDG — the scheduler round-robins
experts across the EP axis exactly like the paper round-robins root tasks
across worker queues. Dropped tokens (over capacity) pass through the
residual, standard for capacity-based MoE.

``moe_impl="dropless"`` has no capacity and drops nothing: the layer holds
experts ``[expert_offset, expert_offset + held_experts)`` of the router's
``num_experts``, routes over all of them, and computes its own experts'
share of the result (the rest lives on other chips of an expert-parallel
deployment). Assignments to held experts are sorted by expert and run
through ragged GEMMs (``jax.lax.ragged_dot``, a Mosaic kernel on TPU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import partition as P_
from . import layers as L

Params = dict


def moe_init(key, cfg: ModelConfig) -> Params:
    """Router over all ``num_experts``; weights of the held experts only."""
    d, f, E = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    Eh = cfg.held_experts
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "router": {"w": L._init_dense(L.key_for(key, "router"), (d, E), dt)},
        "experts": {
            "up": {"w": L._init_dense(L.key_for(key, "eup"), (Eh, d, f), dt, 1)},
            "gate": {"w": L._init_dense(L.key_for(key, "egate"), (Eh, d, f), dt, 1)},
            "down": {"w": L._init_dense(L.key_for(key, "edown"), (Eh, f, d), dt, 1)},
        },
    }
    for i in range(cfg.num_shared_experts):
        p[f"shared{i}"] = L.mlp_init(L.key_for(key, "shared", i), cfg,
                                     d_ff=cfg.shared_ff)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(8, min(n_tokens, math.ceil(c / 8) * 8))


def moe_apply(p: Params, cfg: ModelConfig, x: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss). Dispatches on cfg.moe_impl."""
    if cfg.moe_impl == "dropless":
        return moe_apply_dropless(p, cfg, x)
    if cfg.moe_impl == "shard_map" and P_.active_mesh() is not None \
            and "model" in P_.active_mesh().axis_names:
        return moe_apply_shard_map(p, cfg, x)
    return moe_apply_gspmd(p, cfg, x)


def moe_apply_gspmd(p: Params, cfg: ModelConfig, x: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Baseline: global scatter/gather dispatch, GSPMD-propagated.

    Correct everywhere, but at pod scale the global-index scatter forces the
    partitioner to all-gather the token stream per layer (measured: the
    dominant collective term for 128-expert configs)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    cdt = cfg.compute_dtype
    T = B * S
    xt = x.reshape(T, d)

    logits = jax.lax.dot_general(
        xt.astype(cdt), p["router"]["w"].astype(cdt),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)               # (T, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                                   # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_weight

    # capacity-based positions via stable sort (O(T·K) memory — the one-hot
    # cumsum alternative is O(T·K·E) and unusable at 128 experts)
    C = capacity(cfg, T)
    flat_expert = expert_idx.reshape(-1)                           # (T*K,)
    TK = flat_expert.shape[0]
    sort_idx = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[sort_idx]
    counts = jnp.bincount(flat_expert, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    ranks = jnp.arange(TK, dtype=jnp.int32) - offsets[sorted_expert].astype(jnp.int32)
    pos = jnp.zeros((TK,), jnp.int32).at[sort_idx].set(ranks)
    keep = pos < C

    # dispatch: scatter tokens into (E, C, d)
    tok_ids = jnp.repeat(jnp.arange(T), K)
    safe_pos = jnp.where(keep, pos, C - 1)
    disp = jnp.zeros((E, C, d), cdt)
    contrib = jnp.where(keep[:, None], xt[tok_ids].astype(cdt), 0)
    disp = disp.at[flat_expert, safe_pos].add(contrib)
    disp = P_.constrain(disp, ("experts", None, None))

    # expert GEMMs (grouped matmul kernel)
    up = ops.grouped_matmul(disp, p["experts"]["up"]["w"].astype(cdt))
    gate = ops.grouped_matmul(disp, p["experts"]["gate"]["w"].astype(cdt))
    h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(cdt)
    h = P_.constrain(h, ("experts", None, None))
    eout = ops.grouped_matmul(h, p["experts"]["down"]["w"].astype(cdt))  # (E,C,d)

    # combine: gather expert outputs back to tokens, weighted by gates
    gathered = eout[flat_expert, safe_pos]                          # (T*K, d)
    weights = jnp.where(keep, gate_vals.reshape(-1), 0.0)
    combined = jax.ops.segment_sum(
        gathered.astype(jnp.float32) * weights[:, None], tok_ids, num_segments=T)
    out = combined.astype(cdt).reshape(B, S, d)

    for i in range(cfg.num_shared_experts):
        out = out + L.mlp_apply(p[f"shared{i}"], cfg, x)
    return out, aux


# ---------------------------------------------------------------------------
# Drop-free held-expert layer
# ---------------------------------------------------------------------------

#: Prefill tokens routed per pass: bounds the sorted rows to 4096 * top_k.
TOKEN_BLOCK = 4096


def route(p: Params, cfg: ModelConfig, xt: jax.Array):
    """Softmax over all experts, top-k, renormalised (= softmax over the
    top-k logits). Returns (gates (T, K) f32, experts (T, K), probs)."""
    logits = jax.lax.dot_general(
        xt.astype(cfg.compute_dtype), p["router"]["w"].astype(cfg.compute_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)
    return gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9), experts, probs


def _held_share(xt, local, gates, w_up, w_gate, w_down):
    """The held experts' part of each token's output, in f32.

    xt (T, d); local (T, K) expert ids relative to the first held expert
    (outside ``[0, E_held)``: another chip's expert); gates (T, K).
    Every assignment to a held expert is computed: no capacity, no drops.
    """
    T, d = xt.shape
    K = local.shape[1]
    Eh = w_up.shape[0]
    flat = local.reshape(-1)
    held = (flat >= 0) & (flat < Eh)
    group = jnp.where(held, flat, Eh)                 # others sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
    rows = xt[order // K]                             # (T*K, d) by expert
    dt = xt.dtype
    up = jax.lax.ragged_dot(rows, w_up.astype(dt), sizes)
    gate = jax.lax.ragged_dot(rows, w_gate.astype(dt), sizes)
    h = (jax.nn.silu(gate.astype(jnp.float32))
         * up.astype(jnp.float32)).astype(dt)
    y = jax.lax.ragged_dot(h, w_down.astype(dt), sizes)
    # rows past the last held group belong to no group: never read them
    y = jnp.where((jnp.arange(T * K) < sizes.sum())[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(T, K, d)        # back to (token, k)
    w = jnp.where(held, gates.reshape(-1), 0.0).reshape(T, K)
    return jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w)


@jax.custom_vjp
def held_share(xt, local, gates, w_up, w_gate, w_down):
    return _held_share_batched(xt, local, gates, w_up, w_gate, w_down)


def _held_share_fwd(*args):
    return _held_share_batched(*args), args


def _held_share_bwd(args, g):
    return jax.vjp(_held_share, *args)[1](g)


held_share.defvjp(_held_share_fwd, _held_share_bwd)


@jax.custom_batching.custom_vmap
def _held_share_batched(xt, local, gates, w_up, w_gate, w_down):
    return _held_share(xt, local, gates, w_up, w_gate, w_down)


@_held_share_batched.def_vmap
def _held_share_vmap(axis_size, in_batched, xt, local, gates, w_up, w_gate,
                     w_down):
    """Batched members (a coalesced server step) fold into one token axis,
    so each held expert's weights are read once per step, not per member.
    A plain ``jax.vmap`` of ``ragged_dot`` with a batched lhs and shared
    weights is not implemented in JAX ("vmap over any dim but 0")."""
    if any(in_batched[3:]):
        axes = tuple(0 if b else None for b in in_batched)
        return jax.vmap(_held_share, in_axes=axes)(
            xt, local, gates, w_up, w_gate, w_down), True
    xt, local, gates = (
        a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
        for a, b in zip((xt, local, gates), in_batched[:3]))
    m, T, d = xt.shape
    out = _held_share_batched(xt.reshape(m * T, d), local.reshape(m * T, -1),
                     gates.reshape(m * T, -1), w_up, w_gate, w_down)
    return out.reshape(m, T, d), True


def moe_apply_dropless(p: Params, cfg: ModelConfig, x: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Drop-free MoE over the held experts, plus the shared experts."""
    B, S, d = x.shape
    E = cfg.num_experts
    cdt = cfg.compute_dtype
    T = B * S
    xt = x.reshape(T, d).astype(cdt)
    with jax.named_scope("moe.route"):
        gates, experts, probs = route(p, cfg, xt)
        ce = jnp.mean(jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32), 0)
        aux = E * jnp.sum(jnp.mean(probs, axis=0) * ce) * cfg.router_aux_weight
        local = experts - cfg.expert_offset
    ew = p["experts"]
    weights = (ew["up"]["w"], ew["gate"]["w"], ew["down"]["w"])
    with jax.named_scope("moe.experts"):
        if T > TOKEN_BLOCK and T % TOKEN_BLOCK == 0:
            n = T // TOKEN_BLOCK
            out = jax.lax.map(
                lambda a: held_share(*a, *weights),
                (xt.reshape(n, TOKEN_BLOCK, d),
                 local.reshape(n, TOKEN_BLOCK, -1),
                 gates.reshape(n, TOKEN_BLOCK, -1))).reshape(T, d)
        else:
            out = held_share(xt, local, gates, *weights)
    out = out.astype(cdt).reshape(B, S, d)
    for i in range(cfg.num_shared_experts):
        out = out + L.mlp_apply(p[f"shared{i}"], cfg, x)
    return out, aux


# ---------------------------------------------------------------------------
# shard_map EP implementation (beyond-paper optimization, §Perf iteration 1)
# ---------------------------------------------------------------------------

def moe_apply_shard_map(p: Params, cfg: ModelConfig, x: jax.Array
                        ) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE with *local* dispatch.

    Activations are replicated across the "model" axis (batch-sharded over
    pod/data only), expert weights are sharded over "model". Each device:
      1. computes the (replicated) router for ITS token shard,
      2. builds dispatch buffers for ONLY its local experts — pure local
         gather, zero communication,
      3. runs its local expert GEMMs,
      4. contributes partial combined outputs; one psum over "model" joins.

    Per layer the only cross-device traffic is the (T_local, d) all-reduce —
    vs. the baseline's token-stream all-gathers. This is the paper's static
    root-task distribution applied to experts: placement decided once by the
    sharding, no runtime negotiation.
    """
    from jax.sharding import PartitionSpec as P

    mesh = P_.active_mesh()
    E, K = cfg.num_experts, cfg.top_k
    tp = mesh.shape["model"]
    assert E % tp == 0, (E, tp)
    E_loc = E // tp
    cdt = cfg.compute_dtype
    B, S, d = x.shape

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    def local(xl, router_w, eup, egate, edown):
        # xl: (B_loc, S, d) — this data-row's tokens, replicated over model
        m = jax.lax.axis_index("model")
        Bl = xl.shape[0]
        T = Bl * S
        xt = xl.reshape(T, d)
        logits = jax.lax.dot_general(
            xt.astype(cdt), router_w.astype(cdt),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (T, E) replicated
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), 0)
        aux = E * jnp.sum(me * ce) * cfg.router_aux_weight
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)       # global load-balance loss

        C = capacity(cfg, T)
        flat_expert = expert_idx.reshape(-1)
        TK = flat_expert.shape[0]
        sort_idx = jnp.argsort(flat_expert, stable=True)
        counts = jnp.bincount(flat_expert, length=E)
        offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                   jnp.cumsum(counts)[:-1]])
        ranks = (jnp.arange(TK, dtype=jnp.int32)
                 - offsets[flat_expert[sort_idx]].astype(jnp.int32))
        pos = jnp.zeros((TK,), jnp.int32).at[sort_idx].set(ranks)
        keep = pos < C

        # local experts only: e in [m*E_loc, (m+1)*E_loc)
        local_e = flat_expert - m * E_loc
        mine = (local_e >= 0) & (local_e < E_loc) & keep
        safe_e = jnp.clip(local_e, 0, E_loc - 1)
        safe_pos = jnp.where(mine, pos, C - 1)
        tok_ids = jnp.repeat(jnp.arange(T), K)
        contrib = jnp.where(mine[:, None], xt[tok_ids].astype(cdt), 0)
        disp = jnp.zeros((E_loc, C, d), cdt).at[safe_e, safe_pos].add(contrib)

        up = ops.grouped_matmul(disp, eup.astype(cdt))
        gate = ops.grouped_matmul(disp, egate.astype(cdt))
        h = (jax.nn.silu(gate.astype(jnp.float32))
             * up.astype(jnp.float32)).astype(cdt)
        eout = ops.grouped_matmul(h, edown.astype(cdt))   # (E_loc, C, d)

        gathered = eout[safe_e, safe_pos]                 # (T*K, d)
        weights = jnp.where(mine, gate_vals.reshape(-1), 0.0)
        combined = jax.ops.segment_sum(
            gathered.astype(jnp.float32) * weights[:, None], tok_ids,
            num_segments=T)
        out = jax.lax.psum(combined, "model")             # join over experts
        return out.reshape(Bl, S, d).astype(cdt), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_spec, None, None),              # x: batch-sharded
                  P(None, None),                          # router replicated
                  P("model", None, None),                 # expert shards
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False)
    out, aux = fn(x, p["router"]["w"],
                  p["experts"]["up"]["w"], p["experts"]["gate"]["w"],
                  p["experts"]["down"]["w"])
    for i in range(cfg.num_shared_experts):
        out = out + L.mlp_apply(p[f"shared{i}"], cfg, x)
    return out, aux
