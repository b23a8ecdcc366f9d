"""A plain Qwen2 decoder in float32, the weights it is checked on, and the
comparison of served tokens against it.

Written from the published architecture (Hugging Face ``Qwen2ForCausalLM``):
pre-norm RMSNorm blocks, grouped-query attention with biased q/k/v and
rotary positions (rotate-half, base ``rope_theta``), a SwiGLU MLP, a final
RMSNorm and a head tied to the embedding. Nothing here imports the program.

The weights are made here from the seed, in the layout the served model
reads them and in the type it serves them in, and the reference reads the
same arrays: it takes nothing that the program made. It upcasts one layer
at a time, so f32 weights never sit on the device whole.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS_KEY = "rms_norm_eps"


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"], "hd": d // h,
            "f": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "Vp": (cfg["vocab_size"] + 255) // 256 * 256,
            "theta": cfg["rope_theta"], "eps": cfg[EPS_KEY]}


def make_weights(cfg: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights in one jitted call, laid out as the served model reads
    them: stacked per-layer leaves, ``(in, out)`` matrices, a tied table
    padded to a multiple of 256 rows.

    Matrices are N(0, 1/fan_in); q/k/v biases N(0, 0.1^2) and norm scales
    1 + N(0, 0.1^2), so that neither path is an identity.
    """
    m = dims(cfg)
    L, d, q, kv, f = m["L"], m["d"], m["H"] * m["hd"], m["Hkv"] * m["hd"], m["f"]

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)

        def vec(shape, scale, base=0.0):
            return (base + scale * jax.random.normal(next(ks), shape,
                                                     jnp.float32)).astype(dtype)

        lin = lambda i, o, bias: ({"w": mat((L, i, o), i), "b": vec((L, o), 0.1)}  # noqa: E731
                                  if bias else {"w": mat((L, i, o), i)})
        return {
            "embed": {"table": mat((m["Vp"], d), d)},
            "layers": {
                "norm1": {"scale": vec((L, d), 0.1, 1.0)},
                "attn": {"wq": lin(d, q, True), "wk": lin(d, kv, True),
                         "wv": lin(d, kv, True), "wo": lin(q, d, False)},
                "norm2": {"scale": vec((L, d), 0.1, 1.0)},
                "mlp": {"up": lin(d, f, False), "gate": lin(d, f, False),
                        "down": lin(f, d, False)},
            },
            "final_norm": {"scale": vec((d,), 0.1, 1.0)},
        }

    return make(key)


# ------------------------------------------------------------------ forward

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (B, S, H, hd), pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quantize_fp8(w):
    """Weights through float8 e4m3 with one scale per output column."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _layer(x, layers, i, m, fp8: bool):
    """One decoder block in f32 on layer ``i`` of the stacked weights."""
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        .astype(jnp.float32), layers)
    mat = _quantize_fp8 if fp8 else (lambda w: w)
    B, S, d = x.shape
    H, Hkv, hd = m["H"], m["Hkv"], m["hd"]
    pos = jnp.arange(S)
    h = _rms(x, p["norm1"]["scale"], m["eps"])
    a = p["attn"]
    q = (h @ mat(a["wq"]["w"]) + a["wq"]["b"]).reshape(B, S, H, hd)
    k = (h @ mat(a["wk"]["w"]) + a["wk"]["b"]).reshape(B, S, Hkv, hd)
    v = (h @ mat(a["wv"]["w"]) + a["wv"]["b"]).reshape(B, S, Hkv, hd)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(B, S, H * hd) @ mat(a["wo"]["w"])
    h = _rms(x, p["norm2"]["scale"], m["eps"])
    mlp = p["mlp"]
    g = jax.nn.silu(h @ mat(mlp["gate"]["w"])) * (h @ mat(mlp["up"]["w"]))
    return x + g @ mat(mlp["down"]["w"])


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _layer_jit(x, layers, i, m_items, fp8):
    return _layer(x, layers, i, dict(m_items), fp8)


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _head_jit(x, rows, final_scale, table, m_items, fp8):
    """Logits over the real vocabulary at the gathered (batch, position) rows."""
    m = dict(m_items)
    h = _rms(x[rows[:, 0], rows[:, 1]], final_scale.astype(jnp.float32),
             m["eps"])
    t = table[:m["V"]].astype(jnp.float32)
    if fp8:
        t = _quantize_fp8(t.T).T
    return h @ t.T


def logits_at(weights: dict, cfg: dict, tokens: jax.Array, rows: jax.Array,
              fp8: bool = False) -> jax.Array:
    """Float32 logits of ``tokens`` (B, S) at ``rows`` ((R, 2) batch, position).

    Runs at ``highest`` matmul precision, one layer per call; ``fp8`` puts
    every weight matrix through float8 first (the precision control).
    """
    m = dims(cfg)
    items = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(weights["embed"]["table"], tokens, axis=0).astype(
            jnp.float32)
        for i in range(m["L"]):
            x = _layer_jit(x, weights["layers"], jnp.int32(i), items, fp8)
        return _head_jit(x, rows, weights["final_norm"]["scale"],
                         weights["embed"]["table"], items, fp8)


# ------------------------------------------------------------------ compare

def sequences(samples: list[tuple[list[int], list[int]]], batch: int,
              length: int):
    """Pack (prompt, served) pairs into (batch, length) token rows and the
    (row, position) of each served token's prediction, padded to fixed
    shapes so that the reference compiles once."""
    import numpy as np

    if len(samples) > batch:
        raise ValueError(f"{len(samples)} samples for a batch of {batch}")
    tokens = np.zeros((batch, length), np.int32)
    rows, served = [], []
    for b, (prompt, out) in enumerate(samples):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > length:
            raise ValueError(f"sample of {len(seq)} tokens exceeds {length}")
        tokens[b, :len(seq)] = seq
        for t, tok in enumerate(out):
            rows.append((b, len(prompt) - 1 + t))
            served.append(tok)
    return tokens, np.asarray(rows, np.int32), np.asarray(served, np.int32)


def pad_rows(rows, served, n: int):
    """Pad gathered rows to ``n`` (repeating the first) for a fixed shape."""
    import numpy as np

    k = len(rows)
    if k > n:
        raise ValueError(f"{k} served tokens exceed the {n} compared rows")
    return (np.concatenate([rows, np.repeat(rows[:1], n - k, 0)]),
            np.concatenate([served, np.repeat(served[:1], n - k, 0)]), k)


@jax.jit
def served_gap(ref_logits, served):
    """How far below the reference's best logit each served token lies."""
    best = ref_logits.max(-1)
    chosen = jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
    return best - chosen


@jax.jit
def control_gap(ref_logits, ctl_logits):
    """The same gap for the token the control ranks first."""
    pick = jnp.argmax(ctl_logits, -1)
    return served_gap(ref_logits, pick)
