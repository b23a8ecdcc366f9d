"""The work a cell asks for, counted from shapes: operations and bytes.

These counts are the benchmark's yardstick, so they are the algorithm's
counts and not the implementation's: a Cholesky of order n is n^3/3
operations however the runtime tiles, pads or copies it.
"""
from __future__ import annotations

import math


# ------------------------------------------------------------------ Cholesky

def cholesky_flops(n: int) -> float:
    """Floating-point operations of one Cholesky factorization of order n."""
    return n ** 3 / 3.0


def stacked_bytes(tdg, plan, buffers: dict) -> int:
    """Bytes the fused classes of one replay stack in and slice out.

    Every class that the plan batched (``vmap`` or ``map``) stacks each
    varying argument of its members (pad lanes included) into one array and
    slices every member's outputs back out; shared arguments are broadcast
    and cost nothing. Slot sizes come from the region's inputs, carried
    through the tasks by abstract evaluation once per payload and shape.
    """
    import jax

    def nbytes(v) -> int:
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(v))

    env = {k: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), v)
        for k, v in buffers.items()}
    memo: dict = {}
    total = 0
    for cls in sorted(plan.classes, key=lambda c: c.wave):
        tasks = [tdg.tasks[t] for t in cls.tids]
        ins = [[env[s] for s in t.ins] for t in tasks]
        for t, args in zip(tasks, ins):
            key = (id(t.fn), tuple(
                (tuple(x.shape), str(x.dtype))
                for a in args for x in jax.tree_util.tree_leaves(a)))
            if key not in memo:
                memo[key] = jax.eval_shape(t.fn, *args)
            outs = [memo[key]] if len(t.outs) == 1 else list(memo[key])
            env.update(zip(t.outs, outs))
        if not cls.fused or cls.batcher not in ("vmap", "map"):
            continue
        for i, shared in enumerate(cls.shared):
            if not shared:
                total += sum(nbytes(args[i]) for args in ins)
                total += cls.padded * nbytes(ins[-1][i])
        total += sum(nbytes(env[s]) for t in tasks for s in t.outs)
    return total


# ------------------------------------------------------------------ Qwen2

def qwen2_matmul_flops_per_token(cfg: dict, with_head: bool = True) -> float:
    """2 x the weights one token meets in matrix products (the head once)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * (q + 2 * kv) + q * d + 3 * d * f
    n = cfg["num_hidden_layers"] * per_layer
    if with_head:
        n += cfg["vocab_size"] * d
    return 2.0 * n


def qwen2_attention_flops(cfg: dict, position: int) -> float:
    """Scores and weighted values of one token at ``position`` (0-based),
    which attends to ``position + 1`` keys in every layer."""
    d = cfg["hidden_size"]
    return 4.0 * cfg["num_hidden_layers"] * d * (position + 1)


def qwen2_request_flops(cfg: dict, prompt_len: int, generated: int) -> float:
    """Model operations one request needs: its prompt, then ``generated``
    tokens. Logits are needed at the prompt's last position and at every
    decoded one; positions are summed in closed form."""
    tok = qwen2_matmul_flops_per_token(cfg, with_head=False)
    head = qwen2_matmul_flops_per_token(cfg) - tok
    # prompt tokens sit at 0..P-1; the g-th decode step feeds position P+g-1
    # for g = 1..generated-1 (the first generated token comes from prefill)
    steps = max(generated - 1, 0)
    positions = prompt_len * (prompt_len - 1) / 2
    positions += steps * prompt_len + steps * (steps - 1) / 2
    count = prompt_len + steps
    att = 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * (positions + count)
    return count * tok + (1 + steps) * head + att


def rmsnorm_bytes(cfg: dict, rows: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the RMSNorms over ``rows`` tokens: each of the
    2 per layer plus the final one reads and writes a d-wide row."""
    norms = 2 * cfg["num_hidden_layers"] + 1
    return float(norms) * rows * cfg["hidden_size"] * itemsize * 2


def rmsnorm_flops(cfg: dict, rows: int) -> float:
    """Square, sum, scale and weight: about 4 operations per element."""
    norms = 2 * cfg["num_hidden_layers"] + 1
    return 4.0 * norms * rows * cfg["hidden_size"]
