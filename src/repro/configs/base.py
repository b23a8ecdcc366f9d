"""Config system: model architecture + input-shape configurations.

Every assigned architecture is a ``ModelConfig`` in its own module
(``repro/configs/<id>.py``); shapes are the four assigned input-shape sets.
Configs are plain frozen dataclasses — hashable, printable, serializable —
and every derived quantity (param counts, per-token FLOPs) lives here so the
roofline analysis and the benchmarks share one source of truth.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

Family = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                       # 0 -> d_model // num_heads

    # attention flavor
    attention: Literal["full", "sliding", "chunked"] = "full"
    window: int = 0                         # sliding-window size
    attn_chunk: int = 0                     # chunked-local chunk size
    global_attn_every: int = 0              # every k-th layer is full attn
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0              # GLM partial rotary
    attn_scale: float = 0.0                 # 0 -> head_dim ** -0.5

    # MLP
    mlp: Literal["swiglu", "gelu", "relu2"] = "swiglu"

    # MoE
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                       # per-expert hidden (0 -> d_ff)
    num_shared_experts: int = 0
    shared_d_ff: int = 0                    # shared-expert hidden (0 -> expert_d_ff)
    experts_held: int = 0                   # experts on this chip (0 -> all)
    expert_offset: int = 0                  # first held expert's id
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128

    # hybrid (Hymba): SSM runs in parallel with attention inside each block
    hybrid_ssm: bool = False
    # interleaved hybrid (Granite 4.0-H): each layer's mixer is either
    # "mamba" (Mamba-2) or "attention"; every layer ends in the MoE/MLP.
    # Empty: every layer is the family's homogeneous block.
    layer_types: tuple = ()

    # encoder-decoder (Whisper): stub conv frontend supplies frame embeddings
    encoder_layers: int = 0
    encoder_seq: int = 1500

    # embeddings / scaling (MiniCPM mu-parametrization)
    tie_embeddings: bool = False
    embed_scale: float = 1.0
    residual_scale: float = 1.0             # applied per-block output
    logit_scale: float = 1.0
    norm_eps: float = 1e-6                  # RMSNorm epsilon

    # numerics / lowering
    dtype: str = "bfloat16"                 # activation/compute dtype
    param_dtype: str = "float32"
    attn_q_chunk: int = 2048                # q-chunking of full attention

    # ---- beyond-paper perf knobs (docs/architecture.md) ----
    moe_impl: str = "gspmd"                 # "gspmd" | "shard_map" (EP-local
    #                                         dispatch + psum combine) |
    #                                         "dropless" (held experts, no
    #                                         capacity: sort + ragged GEMMs)
    shard_kv_seq: bool = False              # decode: shard cache length over
    #                                         "model" (MHA-style archs)
    ssm_split_proj: bool = False            # separate z/xBC/dt projections
    #                                         (shard-boundary aligned)
    scan_layers: bool = True                # lax.scan over stacked layers
    remat: Literal["none", "full", "dots"] = "full"
    loss_chunk: int = 0                     # CE in chunks of tokens (0 = off)

    # ---- derived ----------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.num_layers:
                raise ValueError(f"{len(self.layer_types)} layer_types for "
                                 f"{self.num_layers} layers")
            bad = set(self.layer_types) - {"mamba", "attention"}
            if bad:
                raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.expert_offset + self.held_experts > self.num_experts:
            raise ValueError("held experts run past the router's experts")
        if self.held_experts < self.num_experts and self.moe_impl != "dropless":
            raise ValueError("only the dropless expert layer holds a share")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a TP-friendly multiple (MaxText-style)."""
        mult = 256
        return (self.vocab_size + mult - 1) // mult * mult

    @property
    def ssm_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_inner // self.ssm_headdim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def shared_ff(self) -> int:
        return self.shared_d_ff or self.expert_d_ff

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def attention_scale(self) -> float:
        return self.attn_scale or self.head_dim ** -0.5

    def mlp_params(self, d_ff: int) -> int:
        per = 3 if self.mlp == "swiglu" else 2
        return per * self.d_model * d_ff

    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * hd * (self.num_heads + 2 * self.num_kv_heads) + self.num_heads * hd * d

    def ssm_params(self) -> int:
        di, n, g = self.ssm_inner, self.ssm_state, self.ssm_groups
        in_proj = self.d_model * (2 * di + 2 * g * n + self.ssm_heads)
        out_proj = di * self.d_model
        conv = self.ssm_conv * (di + 2 * g * n)
        return in_proj + out_proj + conv + 2 * self.ssm_heads

    def _ffn_params(self, active: bool) -> int:
        if not self.num_experts:
            return self.mlp_params(self.d_ff)
        routed = self.top_k if active else self.held_experts
        return (routed * self.mlp_params(self.expert_d_ff)
                + self.num_shared_experts * self.mlp_params(self.shared_ff)
                + self.d_model * self.num_experts)          # router

    def _block_params(self, layer: int, active: bool) -> int:
        if self.layer_types:
            mixer = (self.ssm_params() if self.layer_types[layer] == "mamba"
                     else self.attn_params())
            return mixer + self._ffn_params(active)
        if self.family == "ssm":
            return self.ssm_params()
        p = self.attn_params()
        if self.hybrid_ssm:
            p += self.ssm_params()
        return p + self._ffn_params(active)

    def block_params(self, layer: int = 0) -> int:
        """Parameters of one decoder block (norms excluded, negligible)."""
        return self._block_params(layer, active=False)

    def active_block_params(self, layer: int = 0) -> int:
        return self._block_params(layer, active=True)

    def param_count(self) -> int:
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        body = sum(self.block_params(i) for i in range(self.num_layers))
        if self.encoder_layers:
            body += self.encoder_layers * (self.attn_params() + self.mlp_params(self.d_ff))
            body += self.num_layers * self.attn_params()  # cross-attention
        return emb + body

    def active_param_count(self) -> int:
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        body = sum(self.active_block_params(i) for i in range(self.num_layers))
        if self.encoder_layers:
            body += self.encoder_layers * (self.attn_params() + self.mlp_params(self.d_ff))
            body += self.num_layers * self.attn_params()
        return emb + body

    def model_flops_per_token(self, seq_len: int, training: bool = True,
                              decode: bool = False) -> float:
        """6·N_active·D convention (fwd 2N + bwd 4N; MoE: active params),
        plus the attention O(S·d) term. ``decode``: one token against a
        seq_len-long context."""
        n = self.active_param_count()
        mult = 6.0 if training else 2.0
        flops = mult * n
        if self.layer_types:
            n_attn = self.layer_types.count("attention")
            n_ssm = self.num_layers - n_attn
        else:
            n_attn = 0 if self.family == "ssm" else self.num_layers
            n_ssm = (self.num_layers if self.family == "ssm" or self.hybrid_ssm
                     else 0)
        # effective kv context seen per token
        if n_attn:
            if decode:
                eff = seq_len
                if self.attention == "sliding" and self.window:
                    eff = min(eff, self.window)
                if self.attention == "chunked" and self.attn_chunk:
                    eff = min(eff, self.attn_chunk)
            else:
                eff = seq_len / 2  # causal average
                if self.attention == "sliding" and self.window:
                    eff = min(eff, self.window)
                if self.attention == "chunked" and self.attn_chunk:
                    eff = min(eff, self.attn_chunk / 2)
            # qk^T and pv matmuls: 2 * 2 * H * hd * eff each fwd
            att = 4.0 * self.num_heads * self.head_dim * eff
            flops += (mult / 2) * n_attn * att
        # SSD state update + readout per token ~ 6 * d_inner * N
        flops += (mult / 2) * n_ssm * 6.0 * self.ssm_inner * self.ssm_state
        return flops


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch           # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def shape_applicable(config: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether an (arch, shape) cell is assigned: ``long_500k`` only where
    every layer's attention is sub-quadratic."""
    if shape.name == "long_500k":
        subquadratic = (config.family == "ssm"
                        or config.hybrid_ssm
                        or (config.attention == "sliding" and config.window > 0))
        if not subquadratic:
            return False, "full-attention arch: long_500k skipped (quadratic)"
    return True, ""


def reduced(config: ModelConfig, **overrides) -> ModelConfig:
    """A tiny same-family config for CPU smoke tests."""
    small = dict(
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=max(1, min(config.num_kv_heads, 2)),
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        window=min(config.window, 32) if config.window else 0,
        attn_chunk=min(config.attn_chunk, 32) if config.attn_chunk else 0,
        num_experts=min(config.num_experts, 4),
        top_k=min(config.top_k, 2),
        moe_d_ff=96 if config.num_experts else 0,
        shared_d_ff=128 if config.shared_d_ff else 0,
        layer_types=("mamba", "attention") if config.layer_types else (),
        experts_held=0,
        expert_offset=0,
        # drop-free capacity: keeps smoke tests deterministic across
        # different token counts (prefill vs teacher-forced forward)
        capacity_factor=float(max(4, config.num_experts and 4)),
        ssm_state=min(config.ssm_state, 16) if config.ssm_state else 0,
        ssm_headdim=16,
        ssm_chunk=16,
        encoder_layers=2 if config.encoder_layers else 0,
        encoder_seq=24 if config.encoder_layers else 1500,
        scan_layers=False,
        remat="none",
        dtype="float32",
        loss_chunk=0,
        name=config.name + "-smoke",
    )
    small.update(overrides)
    return dataclasses.replace(config, **small)
