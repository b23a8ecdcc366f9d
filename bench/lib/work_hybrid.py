"""The work of an interleaved hybrid LM (Granite 4.0-H), counted from its
configuration file's shapes: operations and bytes per token, and of the
two kernels its cell reads, the SSD chunk scan and the held experts' GEMMs.

Routed work is an expectation: a token picks ``num_experts_per_tok`` of the
router's ``router_experts``, so under even routing it meets
``k * held / router_experts`` of the experts held here, and ``n`` tokens
reach ``held * (1 - (1 - k / E)^n)`` distinct held experts.
"""
from __future__ import annotations


def _shape(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    kinds = cfg["layer_types"]
    di = cfg["mamba_expand"] * d
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hd = d // cfg["num_attention_heads"]
    return {"d": d, "di": di, "gn": gn, "mH": cfg["mamba_n_heads"],
            "P": cfg["mamba_d_head"], "N": cfg["mamba_d_state"],
            "Q": cfg["mamba_chunk_size"],
            "q": cfg["num_attention_heads"] * hd,
            "kv": cfg["num_key_value_heads"] * hd,
            "f": cfg["intermediate_size"], "fs": cfg["shared_intermediate_size"],
            "E": cfg["router_experts"], "Eh": cfg["num_local_experts"],
            "k": cfg["num_experts_per_tok"],
            "n_mamba": kinds.count("mamba"), "n_attn": kinds.count("attention"),
            "L": len(kinds), "V": cfg["vocab_size"]}


def routed_rows(cfg: dict, tokens: float) -> float:
    """Expected (token, held expert) assignments of ``tokens`` tokens."""
    s = _shape(cfg)
    return tokens * s["k"] * s["Eh"] / s["E"]


def matmul_flops_per_token(cfg: dict, with_head: bool = True) -> float:
    """2 x the weights one token meets in matrix products: Mamba-2 in/out
    projections, attention projections, router, its expected held experts,
    the shared expert (and the tied head once)."""
    s = _shape(cfg)
    d = s["d"]
    mamba = d * (2 * s["di"] + 2 * s["gn"] + s["mH"]) + s["di"] * d
    attn = d * (s["q"] + 2 * s["kv"]) + s["q"] * d
    ffn = (d * s["E"] + routed_rows(cfg, 1) * 3 * d * s["f"]
           + 3 * d * s["fs"])
    n = s["n_mamba"] * mamba + s["n_attn"] * attn + s["L"] * ffn
    if with_head:
        n += s["V"] * d
    return 2.0 * n


def scan_flops_per_token(cfg: dict) -> float:
    """The selective-state recurrence per token: decay, input and readout
    over the (heads, head dim, state) state, about 6 x d_inner x N."""
    s = _shape(cfg)
    return 6.0 * s["n_mamba"] * s["di"] * s["N"]


def request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """Model operations one request needs: its prompt, then ``generated``
    tokens (the first from prefill). Logits at the prompt's last position
    and at every decoded one; attention scores summed in closed form."""
    s = _shape(cfg)
    tok = matmul_flops_per_token(cfg, with_head=False) + scan_flops_per_token(cfg)
    head = 2.0 * s["V"] * s["d"]
    steps = max(generated - 1, 0)
    count = prompt_len + steps
    positions = prompt_len * (prompt_len - 1) / 2
    positions += steps * prompt_len + steps * (steps - 1) / 2
    att = 4.0 * s["n_attn"] * s["q"] * (positions + count)
    return count * tok + (1 + steps) * head + att


# ------------------------------------------------------------------ kernels

def ssd_kernel_work(cfg: dict, tokens: float) -> tuple[float, float]:
    """(operations, bytes) of the SSD chunk kernel over ``tokens`` prompt
    tokens, as the algorithm needs them: per chunk of Q tokens, C B^T
    (Q x Q x N) once per group, and per head its masked product with x
    (Q x Q x P) and the chunk's end state (Q x N x P); it reads dt*x and
    dt*A per head and B and C per group, and writes y and the state, in
    f32. (The kernel forms C B^T for every head of a group.)"""
    s = _shape(cfg)
    Q, N, P, H = s["Q"], s["N"], s["P"], s["mH"]
    G = s["gn"] // N
    per_token = G * 2.0 * Q * N + H * (2.0 * Q * P + 2.0 * N * P)
    nbytes = 4.0 * (H * (2 * P + 1) + G * 2 * N) + 4.0 * H * N * P / Q
    n = s["n_mamba"] * tokens
    return n * per_token, n * nbytes


def expert_weight_bytes(cfg: dict, experts: float, itemsize: int = 2) -> float:
    """Bytes of ``experts`` experts' up, gate and down matrices, all layers."""
    s = _shape(cfg)
    return s["L"] * experts * 3 * s["d"] * s["f"] * itemsize


def expert_prefill_work(cfg: dict, tokens: float,
                        itemsize: int = 2) -> tuple[float, float]:
    """(operations, bytes) of the held experts' GEMMs over ``tokens`` prompt
    tokens: their expected rows through up, gate and down; the weights once
    and each row's input, hidden and output once."""
    s = _shape(cfg)
    rows = routed_rows(cfg, tokens)
    flops = s["L"] * rows * 6.0 * s["d"] * s["f"]
    nbytes = (expert_weight_bytes(cfg, s["Eh"], itemsize)
              + s["L"] * rows * (2 * s["d"] + 3 * s["f"]) * itemsize)
    return flops, nbytes


def experts_reached(cfg: dict, tokens: int) -> float:
    """Expected distinct held experts that ``tokens`` tokens route to."""
    s = _shape(cfg)
    return s["Eh"] * (1.0 - (1.0 - s["k"] / s["E"]) ** tokens)


def expert_decode_work(cfg: dict, occupancy: dict,
                       itemsize: int = 2) -> list[tuple[float, float]]:
    """(operations, bytes) of one decode step's held-expert GEMMs for each
    step: ``occupancy`` maps members per step to the number of steps. A
    step reads the weights of each held expert its tokens reach."""
    s = _shape(cfg)
    out = []
    for members, steps in occupancy.items():
        rows = routed_rows(cfg, members)
        flops = s["L"] * rows * 6.0 * s["d"] * s["f"]
        nbytes = expert_weight_bytes(cfg, experts_reached(cfg, members), itemsize)
        out.extend([(flops, nbytes)] * steps)
    return out


def least_seconds(work, peaks: dict) -> float:
    """Summed least time of (operations, bytes) pairs on the chip."""
    return sum(max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
               for f, b in work)
