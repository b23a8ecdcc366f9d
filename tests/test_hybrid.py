"""Interleaved hybrid stacks (Granite 4.0-H's shape): the drop-free expert
layer that holds a share of the experts, and per-request state of two kinds
(SSD and conv state on Mamba-2 layers, K/V on attention layers) carried
through RegionServer's coalesced decode steps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import TDG
from repro.models import init_params
from repro.models import layers as L
from repro.models import moe as MoE
from repro.models.model import prefill
from repro.serving import RegionServer
from repro.training import make_serve_step

KEY = jax.random.PRNGKey(7)


def _cfg(**kw):
    base = reduced(get_config("granite-4.0-h-small"), dtype="float32")
    return dataclasses.replace(base, **kw)


def _routed(p, cfg, xt, held):
    """Every token through each of its top-k experts that is ``held``
    (global ids), gated, one expert at a time: no capacity anywhere."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(xt @ p["router"]["w"], -1)
        gates, experts = jax.lax.top_k(probs, cfg.top_k)
        gates = gates / gates.sum(-1, keepdims=True)
        out = jnp.zeros_like(xt)
        e = p["experts"]
        for j, eid in enumerate(held):
            h = (jax.nn.silu(xt @ e["gate"]["w"][j]) * (xt @ e["up"]["w"][j]))
            g = jnp.where(experts == eid, gates, 0.0).sum(-1, keepdims=True)
            out = out + g * (h @ e["down"]["w"][j])
    return out


def test_skewed_routing_drops_no_token():
    """Every token routes to the same two held experts, over a prefill long
    enough to run in token blocks: a capacity-bound layer would keep an
    expert's share of the rows and drop the rest; this one computes all."""
    cfg = _cfg(num_experts=8, top_k=2, experts_held=4, capacity_factor=1.25)
    p = MoE.moe_init(KEY, cfg)
    w = np.zeros((cfg.d_model, cfg.num_experts), np.float32)
    w[:, 1], w[:, 2] = 1.0, 0.5
    p["router"]["w"] = jnp.asarray(w)
    B, S = 2, MoE.TOKEN_BLOCK
    x = jnp.abs(jax.random.normal(KEY, (B, S, cfg.d_model))) + 0.1
    out, _ = MoE.moe_apply(p, cfg, x)
    xt = x.reshape(-1, cfg.d_model)
    assert MoE.capacity(cfg, B * S) < B * S        # the capacity path drops
    want = _routed(p, cfg, xt, range(4)) + L.mlp_apply(p["shared0"], cfg, xt)
    np.testing.assert_allclose(out.reshape(-1, cfg.d_model), want,
                               rtol=2e-4, atol=2e-5)


def test_held_shares_sum_to_the_uncut_layer():
    """Four chips holding two experts each: their outputs, with the shared
    expert (which every chip computes) counted once, add up to the layer
    that holds all eight."""
    uncut = _cfg(num_experts=8, top_k=2, experts_held=0)
    p = MoE.moe_init(KEY, uncut)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 24, uncut.d_model))
    whole, _ = MoE.moe_apply(p, uncut, x)
    shared = L.mlp_apply(p["shared0"], uncut, x)
    total = jnp.zeros_like(whole)
    for chip in range(4):
        cfg = dataclasses.replace(uncut, experts_held=2,
                                  expert_offset=2 * chip)
        mine = dict(p, experts=jax.tree_util.tree_map(
            lambda a, c=chip: a[2 * c:2 * c + 2], p["experts"]))
        part, _ = MoE.moe_apply(mine, cfg, x)
        total = total + part - shared
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4, atol=2e-5)
    xt = x.reshape(-1, uncut.d_model)
    want = _routed(p, uncut, xt, range(8)) + shared.reshape(xt.shape)
    np.testing.assert_allclose(whole.reshape(xt.shape), want,
                               rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError):      # the capacity path indexes all E
        dataclasses.replace(uncut, experts_held=2, moe_impl="gspmd")


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its own."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_batched_members_share_one_pass_over_the_held_experts():
    """A coalesced step vmaps the expert layer over its members: they fold
    into one token axis (three ragged GEMMs over every member's rows, so
    each held expert's weights are read once a step), and each member gets
    what its own pass gives."""
    cfg = _cfg(num_experts=8, top_k=2, experts_held=4, expert_offset=2)
    p = MoE.moe_init(KEY, cfg)
    m, T = 5, 3
    xt = jax.random.normal(jax.random.fold_in(KEY, 2), (m, T, cfg.d_model))
    gates, experts, _ = MoE.route(p, cfg, xt.reshape(m * T, -1))
    local = (experts - cfg.expert_offset).reshape(m, T, -1)
    gates = gates.reshape(m, T, -1)
    w = (p["experts"]["up"]["w"], p["experts"]["gate"]["w"],
         p["experts"]["down"]["w"])
    batched = jax.vmap(MoE.held_share, in_axes=(0, 0, 0, None, None, None))
    jaxpr = jax.make_jaxpr(batched)(xt, local, gates, *w)
    rows = [e.invars[0].aval.shape[0] for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name.startswith("ragged_dot")]
    assert rows == [m * T * cfg.top_k] * 3
    got = batched(xt, local, gates, *w)
    for i in range(m):
        np.testing.assert_allclose(
            got[i], MoE._held_share(xt[i], local[i], gates[i], *w),
            rtol=1e-5, atol=1e-6)


def test_mixed_state_members_join_and_leave_a_coalesced_step():
    """Two slots, each a Mamba-2 and an attention layer's state, step
    together, then one alone, together again, then the other alone; each
    member gets back the tokens and state a lone step on its own state
    gives."""
    cfg = _cfg(experts_held=2)
    assert cfg.layer_types == ("mamba", "attention")
    params = init_params(cfg, KEY)
    step = make_serve_step(cfg)
    lone = jax.jit(step)
    server = RegionServer(max_batch=2, max_wait_ms=50.0, name="hybrid-test",
                          adaptive=False)
    for i in range(2):
        tdg = TDG(f"decode[{i}]")
        tdg.add_task(step, ins=["params", "tokens", "pos", "caches"],
                     outs=["next", "caches"], name="decode")
        server.register_tenant(f"slot{i}", tdg, outputs=("next", "caches"))
    state = []
    for i, n in enumerate((8, 16)):
        prompt = jax.random.randint(jax.random.fold_in(KEY, 10 + i), (1, n),
                                    0, cfg.vocab_size)
        logits, caches, pos = prefill(params, cfg, {"tokens": prompt}, 32)
        state.append((jnp.argmax(logits[:, -1], -1).astype(jnp.int32),
                      pos, caches))
    kinds = [sorted(c) for c in state[0][2]]
    assert kinds == [["ssm"], ["attn"]]
    try:
        for who in ((0, 1), (0,), (0, 1), (1,)):
            futures = server.submit_many([(f"slot{i}", {
                "params": params, "tokens": state[i][0][:, None],
                "pos": state[i][1], "caches": state[i][2]}) for i in who])
            for i, f in zip(who, futures):
                out = f.result(timeout=120)
                tok, pos, caches = state[i]
                nxt, want = lone(params, tok[:, None], pos, caches)
                assert int(out["next"][0]) == int(nxt[0])
                jax.tree_util.tree_map(
                    lambda a, b: np.testing.assert_allclose(
                        a, b, rtol=1e-4, atol=1e-5), out["caches"], want)
                state[i] = (out["next"], pos + 1, out["caches"])
        m = server.metrics.snapshot()
        assert m["batch_occupancy_max"] == 2 and m["batch_fallbacks"] == 0
    finally:
        server.close()
    ssd = [s[2][0]["ssm"]["ssd"] for s in state]
    assert not np.allclose(ssd[0], ssd[1])     # the members' states differ
