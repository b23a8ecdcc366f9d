"""Train/serve step construction.

Two granularities (docs/architecture.md):

* ``make_train_step`` — the production path: one pure function
  (fwd + bwd + clip + AdamW), replay-compiled once and re-executed every
  step. This is the whole-region TDG replay (the paper's execute_TDG) at
  step granularity; XLA owns overlap/fusion inside.

* ``make_tdg_train_region`` — the paper-faithful fine-grained path: the
  step expressed as a TaskGraphRegion whose tasks are embed / per-layer
  fwd / per-layer bwd (recompute-style VJP) / loss / grad-accumulate /
  optimizer update. Used by the paper-mirror benchmarks (eager-vs-replay)
  and the examples; numerically equal to the fused step.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import TaskGraphRegion, taskgraph
from ..models import layers as L
from ..models import model as M
from ..models import transformer as T
from ..optim import adamw as _adamw_mod  # noqa: F401
from ..optim.adamw import Optimizer, apply_updates


def make_train_step(cfg: ModelConfig, optimizer: Optimizer):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch), has_aux=True)(params)
        updates, opt_state, opt_metrics = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = {**metrics, **opt_metrics}
        return params, opt_state, metrics

    return train_step


def make_serve_step(cfg: ModelConfig):
    """(params, tokens (B,1), pos (B,), caches) -> (next_tokens, new_caches)."""

    def serve_step(params, tokens, pos, caches):
        logits, caches = M.decode_step(params, cfg, tokens, pos, caches)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, caches

    return serve_step


# ---------------------------------------------------------------------------
# Fine-grained TDG step (per-layer fwd/bwd tasks)
# ---------------------------------------------------------------------------

def make_tdg_train_region(cfg: ModelConfig, optimizer: Optimizer,
                          name: str = "tdg_train_step") -> TaskGraphRegion:
    """Build the per-layer task region. Buffers:
    in : params (pytree slot), opt_state, tokens
    out: params, opt_state, loss
    """
    n = cfg.num_layers

    def build(g, params, opt_state, tokens):
        # embed task
        def embed_fn(p, toks):
            B, Sq = toks.shape
            pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
            x = L.embed(p["embed"], toks, cfg.compute_dtype) * cfg.embed_scale
            return x, pos
        g.task(embed_fn, ins=["params", "tokens"], outs=["x0", "positions"],
               name="embed")

        # forward chain
        for i in range(n):
            def fwd(p, x, positions, _i=i):
                lp = jax.tree_util.tree_map(lambda a: a[_i], p["layers"])
                y, aux, _ = T.block_apply(lp, cfg, x, positions, layer_idx=_i)
                return y, aux
            g.task(fwd, ins=["params", f"x{i}", "positions"],
                   outs=[f"x{i + 1}", f"aux{i}"], name=f"fwd_L{i}")

        # loss head (+ grad wrt final hidden) as one task
        def head_loss(p, xn, toks, *auxes):
            def f(xn_):
                h = T._norm(cfg, p["final_norm"], xn_)
                table = p["embed"] if cfg.tie_embeddings else p["head"]
                logits = L.unembed(table, h, cfg.compute_dtype) * cfg.logit_scale
                labels = jnp.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
                mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
                return ((lse - gold) * mask).sum() / mask.sum()
            ce, gxn = jax.value_and_grad(f)(xn)
            loss = ce + sum(auxes)
            return loss, gxn
        g.task(head_loss,
               ins=["params", f"x{n}", "tokens"] + [f"aux{i}" for i in range(n)],
               outs=["loss", f"gx{n}"], name="head_loss")

        # head/embed/final_norm param grads (recompute VJP)
        def head_bwd(p, xn, toks):
            def f(fn_, tab_):
                h = L.rmsnorm(fn_, xn) if cfg.family != "encdec" else L.layernorm(fn_, xn)
                logits = L.unembed(tab_, h, cfg.compute_dtype) * cfg.logit_scale
                labels = jnp.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
                mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
                lse = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
                return ((lse - gold) * mask).sum() / mask.sum()
            table = p["embed"] if cfg.tie_embeddings else p["head"]
            _, vjp = jax.vjp(f, p["final_norm"], table)
            gfn, gtab = vjp(jnp.ones(()))
            return gfn, gtab
        g.task(head_bwd, ins=["params", f"x{n}", "tokens"],
               outs=["g_final_norm", "g_table"], name="head_bwd")

        # backward chain (one task per layer; recompute inside)
        for i in reversed(range(n)):
            def bwd(p, x, positions, gy, _i=i):
                lp = jax.tree_util.tree_map(lambda a: a[_i], p["layers"])
                def f(lp_, x_):
                    y, aux, _ = T.block_apply(lp_, cfg, x_, positions, layer_idx=_i)
                    return y, aux
                _, vjp = jax.vjp(f, lp, x)
                glp, gx = vjp((gy, jnp.ones((), jnp.float32)))
                return gx, glp
            g.task(bwd, ins=["params", f"x{i}", "positions", f"gx{i + 1}"],
                   outs=[f"gx{i}", f"glayer{i}"], name=f"bwd_L{i}")

        # embedding grad from gx0 + head grads
        def embed_bwd(p, toks, gx0, gtab, gfn):
            def f(emb_):
                return (L.embed(emb_, toks, cfg.compute_dtype)
                        * cfg.embed_scale).astype(jnp.float32)
            _, vjp = jax.vjp(f, p["embed"])
            (gemb,) = vjp(gx0.astype(jnp.float32))
            if cfg.tie_embeddings:
                gemb = jax.tree_util.tree_map(
                    lambda a, b: a + b, gemb, gtab)
                ghead = None
            else:
                ghead = gtab
            return gemb, ghead, gfn
        g.task(embed_bwd, ins=["params", "tokens", "gx0", "g_table",
                               "g_final_norm"],
               outs=["g_embed", "g_head", "g_final_norm2"], name="embed_bwd")

        # assemble grads + optimizer update
        def opt_update(p, s, gemb, ghead, gfn, *glayers):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *glayers)
            grads = {"embed": gemb, "layers": stacked, "final_norm": gfn}
            if not cfg.tie_embeddings:
                grads["head"] = ghead
            updates, s2, _m = optimizer.update(grads, s, p)
            p2 = apply_updates(p, updates)
            return p2, s2
        g.task(opt_update,
               ins=["params", "opt_state", "g_embed", "g_head",
                    "g_final_norm2"] + [f"glayer{i}" for i in range(n)],
               outs=["params", "opt_state"], name="opt_update")

    return TaskGraphRegion(build, name=name,
                           donate_slots=("params", "opt_state"),
                           outputs=("params", "opt_state", "loss"))
