"""Model API: init / forward / loss (training) and prefill / decode (serving).

The step functions here are the payloads that the Taskgraph runtime records
and replays: shape-stable, pure, repeatedly executed — exactly the paper's
"recurrent taskgraph" profile (§4.2.3).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import partition as P_
from . import layers as L
from . import ssm as S
from . import transformer as T

Params = dict


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    p: Params = {
        "embed": L.embedding_init(L.key_for(key, "embed"), cfg.padded_vocab,
                                  cfg.d_model, jnp.dtype(cfg.param_dtype)),
        "layers": T.layers_init(L.key_for(key, "layers"), cfg),
        "final_norm": T._norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.embedding_init(L.key_for(key, "head"), cfg.padded_vocab,
                                     cfg.d_model, jnp.dtype(cfg.param_dtype))
    if cfg.encoder_layers:
        p["encoder"] = T.stack_init(L.key_for(key, "enc"), cfg,
                                    cfg.encoder_layers, T.encoder_block_init)
        p["enc_norm"] = T._norm_init(cfg, cfg.d_model)
    return p


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _sinusoidal_at(positions: jax.Array, d: int) -> jax.Array:
    """(B, S) int positions -> (B, S, d) sinusoidal embeddings (traceable)."""
    pos = positions.astype(jnp.float32)[..., None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, None, :]
    ang = pos / jnp.power(10000.0, 2.0 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, Se, d)."""
    x = frames.astype(cfg.compute_dtype)
    x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
    x = T.encoder_stack(params["encoder"], cfg, x)
    return T._norm(cfg, params["enc_norm"], x)


def hidden_states(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  positions: jax.Array | None = None,
                  enc_out: jax.Array | None = None,
                  mode: str = "train", caches: list | None = None):
    B, Sq = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None],
                                     (B, Sq))
    x = L.embed(params["embed"], tokens, cfg.compute_dtype) * cfg.embed_scale
    if cfg.family == "encdec" and cfg.rope_theta <= 0:
        # absolute sinusoidal positions, computed from the (possibly traced)
        # position ids so decode steps get the right phase
        x = x + _sinusoidal_at(positions, cfg.d_model).astype(x.dtype)
    x = P_.constrain(x, ("batch", None, None))
    x, aux, caches = T.decoder_stack(params["layers"], cfg, x, positions,
                                     mode=mode, caches=caches, enc_out=enc_out)
    x = T._norm(cfg, params["final_norm"], x)
    return x, aux, caches


def _logits(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = L.unembed(table, hidden, cfg.compute_dtype) * cfg.logit_scale
    if cfg.padded_vocab != cfg.vocab_size:   # mask pad columns out of softmax
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return P_.constrain(logits, ("batch", None, "vocab"))


def forward(params: Params, cfg: ModelConfig, batch: dict) -> tuple[jax.Array, jax.Array]:
    """Full logits (B, S, V) — use loss_fn for training (chunked CE)."""
    enc_out = (_encode(params, cfg, batch["frames"])
               if cfg.family == "encdec" else None)
    h, aux, _ = hidden_states(params, cfg, batch["tokens"], enc_out=enc_out)
    return _logits(params, cfg, h), aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _ce_chunk(params, cfg, hidden, labels, mask):
    logits = _logits(params, cfg, hidden)                 # (B, s, V) f32
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold) * mask
    return ce.sum(), mask.sum()


def loss_fn(params: Params, cfg: ModelConfig, batch: dict):
    """Next-token CE (+ MoE aux). Big-vocab safe: CE over sequence chunks."""
    tokens = batch["tokens"]
    enc_out = (_encode(params, cfg, batch["frames"])
               if cfg.family == "encdec" else None)
    h, aux, _ = hidden_states(params, cfg, tokens, enc_out=enc_out)
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.ones_like(labels, jnp.float32).at[:, -1].set(0.0)
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"].astype(jnp.float32)

    B, Sq = tokens.shape
    chunk = cfg.loss_chunk
    if chunk and Sq % chunk == 0 and Sq > chunk:
        # python loop (not lax.scan): full logits never materialize, each
        # chunk's logits are rematerialized in the backward pass, and the
        # dry-run cost analysis stays exact (scan bodies are counted once).
        nc = Sq // chunk
        tot, cnt = jnp.zeros(()), jnp.zeros(())
        ck = jax.checkpoint(
            lambda hc, lc, mc: _ce_chunk(params, cfg, hc, lc, mc))
        for i in range(nc):
            s, n = ck(h[:, i * chunk:(i + 1) * chunk],
                      labels[:, i * chunk:(i + 1) * chunk],
                      mask[:, i * chunk:(i + 1) * chunk])
            tot, cnt = tot + s, cnt + n
    else:
        tot, cnt = _ce_chunk(params, cfg, h, labels, mask)
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux, "tokens": cnt}


# ---------------------------------------------------------------------------
# Serving: caches, prefill, decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """Per-layer decode state: SSD and conv state on a Mamba-2 mixer, a K/V
    ring on an attention layer (both on Hymba's parallel blocks)."""
    caches = []
    for i in range(cfg.num_layers):
        c: dict[str, Any] = {}
        if cfg.family == "ssm" or cfg.layer_types[i:i + 1] == ("mamba",):
            c["ssm"] = S.init_ssm_state(cfg, batch)
        else:
            c["attn"] = L.init_attn_cache(cfg, i, batch, max_len)
            if cfg.hybrid_ssm:
                c["ssm"] = S.init_ssm_state(cfg, batch)
            if cfg.family == "encdec":
                Hkv, hd = cfg.num_kv_heads, cfg.head_dim
                c["cross_kv"] = {
                    "k": jnp.zeros((batch, cfg.encoder_seq, Hkv, hd),
                                   cfg.compute_dtype),
                    "v": jnp.zeros((batch, cfg.encoder_seq, Hkv, hd),
                                   cfg.compute_dtype),
                }
        caches.append(c)
    return caches


def prefill(params: Params, cfg: ModelConfig, batch: dict, max_len: int):
    """Process the prompt; returns (last-token logits, caches, next_pos)."""
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    enc_out = (_encode(params, cfg, batch["frames"])
               if cfg.family == "encdec" else None)
    caches = init_caches(cfg, B, max_len)
    h, _, caches = hidden_states(params, cfg, tokens, enc_out=enc_out,
                                 mode="prefill", caches=caches)
    logits = _logits(params, cfg, h[:, -1:])
    return logits, caches, jnp.full((B,), Sq, jnp.int32)


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                pos: jax.Array, caches: list):
    """One token per sequence: tokens (B, 1), pos (B,). Returns
    (logits (B, 1, V), new_caches)."""
    positions = pos[:, None]
    h, _, caches = hidden_states(params, cfg, tokens, positions=positions,
                                 mode="decode", caches=caches)
    return _logits(params, cfg, h), caches


def greedy_decode(params: Params, cfg: ModelConfig, batch: dict,
                  steps: int, max_len: int):
    """Simple serving loop used by examples/tests (jit the inner step)."""
    logits, caches, pos = prefill(params, cfg, batch, max_len)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    outs = [tok]
    step = jax.jit(lambda p, t, ps, c: decode_step(p, cfg, t, ps, c))
    for _ in range(steps - 1):
        logits, caches = step(params, tok[:, None], pos, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        pos = pos + 1
        outs.append(tok)
    return jnp.stack(outs, axis=1)
