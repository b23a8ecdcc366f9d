"""A plain Granite 4.0-H decoder in float32, the weights it is checked on,
and its logits at chosen positions.

Written from the published architecture (Hugging Face
``GraniteMoeHybridForCausalLM``): each layer is

    x += residual_multiplier * mixer(rmsnorm(x))
    x += residual_multiplier * (moe(h) + shared_mlp(h)),  h = rmsnorm(x)

where the mixer is a Mamba-2 block (in_proj to [z | xBC | dt], causal
depthwise conv with bias and SiLU over xBC, the selective state recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, a
gated RMSNorm y * silu(z) over d_inner, out_proj) or grouped-query attention
with no positional encoding, scaled by ``attention_multiplier``. The router
is a softmax over the top-k logits; experts and the shared expert are
SwiGLU. Embeddings are multiplied by ``embedding_multiplier`` and logits
divided by ``logits_scaling``; the head is tied to the embedding. Nothing
here imports the program.

Departures, each for the one-chip cut the configuration file states:

* The expert layer holds experts ``[held_expert_offset, + num_local_experts)``
  of the router's ``router_experts``. The router ranks all of them; only
  the held experts' gated outputs are summed, with no capacity, as the
  program computes its chip's share. The absent experts' part is left out.
* The Mamba-2 recurrence runs token by token (``lax.scan``), not in the
  chunked (SSD) form the program's kernel uses.
* Attention runs over blocks of queries and the experts over blocks of
  tokens, and each sequence runs alone, padded at its end to a multiple of
  ``pad_to`` tokens (causal: the padding changes no earlier position), so
  that a 16k-token prompt fits the chip beside the served weights.

The weights are made here from the seed, in the layout the served model
reads them and in the type it serves them in. The reference upcasts one
layer at a time, so f32 weights never sit on the device whole.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen2 import _quantize_fp8

MIXERS = ("mamba", "attention")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    H = cfg["mamba_n_heads"]
    return {"d": d, "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"],
            "V": cfg["vocab_size"], "Vp": (cfg["vocab_size"] + 255) // 256 * 256,
            "di": di, "mH": H, "P": cfg["mamba_d_head"], "G": G, "N": N,
            "K": cfg["mamba_d_conv"], "conv_ch": di + 2 * G * N,
            "in_dim": 2 * di + 2 * G * N + H,
            "E": cfg["router_experts"], "Eh": cfg["num_local_experts"],
            "off": cfg.get("held_expert_offset", 0),
            "topk": cfg["num_experts_per_tok"], "f": cfg["intermediate_size"],
            "fs": cfg["shared_intermediate_size"],
            "eps": cfg["rms_norm_eps"], "emb": cfg["embedding_multiplier"],
            "res": cfg["residual_multiplier"], "logit_div": cfg["logits_scaling"],
            "attn_scale": cfg["attention_multiplier"]}


def layer_types(cfg: dict) -> list[str]:
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(MIXERS):
        raise ValueError("layer_types must name a mixer for every layer")
    return kinds


def make_weights(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in one jitted call, laid out as the served model reads
    them: a list of per-layer dicts, each with its own mixer's weights only,
    ``(in, out)`` matrices, the held experts' matrices ``(experts, in,
    out)``, a tied table padded to a multiple of 256 rows.

    Matrices are N(0, 1/fan_in); norm scales and D are 1 + N(0, 0.1^2),
    conv biases N(0, 0.1^2); A = -exp(A_log) with A in [-16, -1] and
    dt_bias the inverse softplus of dt in [0.001, 0.1], as Mamba-2
    initialises them. The table is N(0, 1/d) over ``embedding_multiplier``:
    the embedded token then enters the residual stream at the scale of one
    layer's output and, through the tied head, does not outweigh what the
    layers add (a larger table makes each served token repeat its input).
    """
    m = dims(cfg)
    kinds = layer_types(cfg)
    d, Eh, f, fs, H = m["d"], m["Eh"], m["f"], m["fs"], m["mH"]
    q, kv = m["H"] * m["hd"], m["Hkv"] * m["hd"]

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 32 * len(kinds) + 2))

        def normal(shape):
            return jax.random.normal(next(ks), shape, jnp.float32)

        def mat(shape, fan_in):
            return (normal(shape) / math.sqrt(fan_in)).astype(dtype)

        def vec(shape, scale, base=0.0):
            return (base + scale * normal(shape)).astype(dtype)

        def mamba():
            u = jax.random.uniform(next(ks), (H,), jnp.float32)
            dt = jnp.exp(math.log(1e-3) + u * (math.log(0.1) - math.log(1e-3)))
            a = 1.0 + 15.0 * jax.random.uniform(next(ks), (H,), jnp.float32)
            return {"ssm": {
                "in_proj": {"w": mat((d, m["in_dim"]), d)},
                "conv": {"w": mat((m["K"], m["conv_ch"]), m["K"]),
                         "b": vec((m["conv_ch"],), 0.1)},
                "A_log": jnp.log(a).astype(dtype),
                "D": vec((H,), 0.1, 1.0),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "norm": {"scale": vec((m["di"],), 0.1, 1.0)},
                "out_proj": {"w": mat((m["di"], d), m["di"])}}}

        def attention():
            return {"attn": {"wq": {"w": mat((d, q), d)},
                             "wk": {"w": mat((d, kv), d)},
                             "wv": {"w": mat((d, kv), d)},
                             "wo": {"w": mat((q, d), q)}}}

        def layer(kind):
            return {"norm1": {"scale": vec((d,), 0.1, 1.0)},
                    **(mamba() if kind == "mamba" else attention()),
                    "norm2": {"scale": vec((d,), 0.1, 1.0)},
                    "moe": {
                        "router": {"w": mat((d, m["E"]), d)},
                        "experts": {"up": {"w": mat((Eh, d, f), d)},
                                    "gate": {"w": mat((Eh, d, f), d)},
                                    "down": {"w": mat((Eh, f, d), f)}},
                        "shared0": {"up": {"w": mat((d, fs), d)},
                                    "gate": {"w": mat((d, fs), d)},
                                    "down": {"w": mat((fs, d), fs)}}}}

        layers = [layer(kind) for kind in kinds]
        table = normal((m["Vp"], d)) / (math.sqrt(d) * m["emb"])
        return {"embed": {"table": table.astype(dtype)},
                "layers": layers,
                "final_norm": {"scale": vec((d,), 0.1, 1.0)}}

    return make(key)


# ------------------------------------------------------------------ forward

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _upcast(layer, fp8: bool):
    """One layer's weights in f32; ``fp8`` puts every matrix but the conv
    through float8 first (the precision control)."""
    def one(path, a):
        a = a.astype(jnp.float32)
        name = jax.tree_util.keystr(path)
        if fp8 and "['w']" in name and "conv" not in name:
            a = _quantize_fp8(a)
        return a
    return jax.tree_util.tree_map_with_path(one, layer)


def _blocks(n: int, size: int) -> int:
    size = min(size, n)
    if n % size:
        raise ValueError(f"length {n} is no multiple of the block {size}")
    return size


def _mamba(x, p, m):
    """One Mamba-2 mixer on a sequence x (S, d), state from zero."""
    S = x.shape[0]
    di, H, P, G, N, K = m["di"], m["mH"], m["P"], m["G"], m["N"], m["K"]
    proj = x @ p["in_proj"]["w"]
    z, xbc, dt = proj[:, :di], proj[:, di:di + m["conv_ch"]], proj[:, -H:]
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(pad[k:k + S] * p["conv"]["w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv"]["b"])
    xs = xbc[:, :di].reshape(S, H, P)
    Bm = xbc[:, di:di + G * N].reshape(S, G, N)
    Cm = xbc[:, di + G * N:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (S, H)
    A = -jnp.exp(p["A_log"])                                     # (H,)

    def step(h, inp):                    # head i reads group i // (H / G)
        dt_t, x_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(t, H // G, axis=0) for t in (b_t, c_t))
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (dt, xs, Bm, Cm), unroll=4)
    y = (y + p["D"][None, :, None] * xs).reshape(S, di)
    y = _rms(y * jax.nn.silu(z), p["norm"]["scale"], m["eps"])
    return y @ p["out_proj"]["w"]


def _attention(x, p, m, q_block=256):
    """Causal NoPE GQA on x (S, d), over blocks of queries."""
    S = x.shape[0]
    H, Hkv, hd = m["H"], m["Hkv"], m["hd"]
    q = (x @ p["wq"]["w"]).reshape(S, H, hd)
    k = jnp.repeat((x @ p["wk"]["w"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((x @ p["wv"]["w"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    qb = _blocks(S, q_block)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * m["attn_scale"]
        pos = i * qb + jnp.arange(qb)
        s = jnp.where(pos[None, :, None] >= jnp.arange(S)[None, None, :], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * hd)
    return o @ p["wo"]["w"]


def _swiglu(h, up, gate, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _moe(h, p, m, t_block=512):
    """Held experts' gated outputs plus the shared expert, over blocks of
    tokens; every token meets every held expert it was routed to."""
    S = h.shape[0]
    tb = _blocks(S, t_block)
    e = p["experts"]

    def block(hb):
        logits = hb @ p["router"]["w"]                            # (tb, E)
        top, idx = jax.lax.top_k(logits, m["topk"])
        gates = jnp.zeros_like(logits).at[
            jnp.arange(hb.shape[0])[:, None], idx].set(jax.nn.softmax(top, -1))
        held = jax.lax.dynamic_slice_in_dim(gates, m["off"], m["Eh"], axis=1)
        ye = jax.vmap(lambda u, g, dn: _swiglu(hb, u, g, dn))(
            e["up"]["w"], e["gate"]["w"], e["down"]["w"])         # (Eh, tb, d)
        return jnp.einsum("etd,te->td", ye, held)

    y = jax.lax.map(block, h.reshape(S // tb, tb, -1)).reshape(S, -1)
    s = p["shared0"]
    return y + _swiglu(h, s["up"]["w"], s["gate"]["w"], s["down"]["w"])


def _layer(x, layer, kind, m, fp8: bool):
    p = _upcast(layer, fp8)
    h = _rms(x, p["norm1"]["scale"], m["eps"])
    mix = _mamba(h, p["ssm"], m) if kind == "mamba" else _attention(h, p["attn"], m)
    x = x + m["res"] * mix
    h = _rms(x, p["norm2"]["scale"], m["eps"])
    return x + m["res"] * _moe(h, p["moe"], m)


@functools.partial(jax.jit, static_argnames=("kind", "m_items", "fp8"))
def _layer_jit(x, layer, kind, m_items, fp8):
    return _layer(x, layer, kind, dict(m_items), fp8)


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _head_jit(x, at, final_scale, table, m_items, fp8):
    """Logits over the real vocabulary at positions ``at`` of x (S, d)."""
    m = dict(m_items)
    h = _rms(x[at], final_scale.astype(jnp.float32), m["eps"])
    t = table[:m["V"]].astype(jnp.float32)
    if fp8:
        t = _quantize_fp8(t.T).T
    return (h @ t.T) / m["logit_div"]


def logits_at(weights: dict, cfg: dict, tokens, rows, fp8: bool = False,
              pad_to: int = 512) -> jax.Array:
    """Float32 logits of ``tokens`` (B, S) at ``rows`` ((R, 2) batch,
    position), in the order of ``rows``.

    Each sequence runs alone, cut after its last compared position and
    padded to a multiple of ``pad_to`` (at most S, a multiple of the
    query and token blocks), one layer per call at
    ``highest`` matmul precision; ``fp8`` is the precision control.
    """
    m = dims(cfg)
    items = tuple(sorted(m.items()))
    kinds = layer_types(cfg)
    tokens, rows = np.asarray(tokens), np.asarray(rows)
    S, R = tokens.shape[1], len(rows)
    parts, order = [], []
    with jax.default_matmul_precision("highest"):
        for b in sorted(set(rows[:, 0].tolist())):
            sel = np.nonzero(rows[:, 0] == b)[0]
            need = int(rows[sel, 1].max()) + 1
            n = min(S, -(-need // pad_to) * pad_to)
            x = jnp.take(weights["embed"]["table"], jnp.asarray(tokens[b, :n]),
                         axis=0).astype(jnp.float32) * m["emb"]
            for kind, layer in zip(kinds, weights["layers"]):
                x = _layer_jit(x, layer, kind, items, fp8)
            at = np.resize(rows[sel, 1], R)         # one shape for every b
            lg = _head_jit(x, jnp.asarray(at), weights["final_norm"]["scale"],
                           weights["embed"]["table"], items, fp8)
            parts.append(lg[:len(sel)])
            order.extend(sel.tolist())
    return jnp.concatenate(parts)[jnp.asarray(np.argsort(order))]
