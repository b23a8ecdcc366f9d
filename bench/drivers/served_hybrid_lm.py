"""Driver for a served interleaved hybrid language model (Granite 4.0-H):
Mamba-2 and attention mixers in one stack, a drop-free expert layer that
holds a share of the experts, and two kinds of per-request state.

The path is the ``served_lm`` driver's: each request takes one slot, a
``RegionServer`` tenant of batch 1; it is prefilled with
``launch.serve.prefill_jit`` and decoded one ``RegionServer.serve`` step
per token, and structurally identical steps of busy slots coalesce into one
batched replay. What differs is the model (``model_config``), the reference
the served tokens are checked on (``bench/reference/granite_hybrid.py``)
and the counters: the bytes of state a member carries through a step
(SSD, conv and K/V), and the step occupancies the expert kernel's work is
counted from.

Set-up makes the weights from the seed and warms every program the window
can run: a prefill per prompt length, the lone decode step and the batched
step at every occupancy bucket up to the most requests that can decode at
once.
"""
from __future__ import annotations

import gc
import math

from bench.drivers.served_lm import (Chat, Record, _bytes_in_use,
                                     _check_layout, _sample, _serve,
                                     nearest_rank)
from bench.lib import harness, work_hybrid
from bench.lib.seeds import jax_key
from bench.reference import granite_hybrid, qwen2


def model_config(cfg: dict):
    """The program's model config for a Granite 4.0-H configuration file."""
    from repro.configs.base import ModelConfig

    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]
            or cfg["position_embedding_type"] != "nope"
            or cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            != cfg["mamba_expand"] * cfg["hidden_size"]):
        raise ValueError("the served_hybrid_lm driver runs Granite 4.0-H "
                         "shaped models only")
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        num_layers=cfg["num_hidden_layers"],
        layer_types=tuple(cfg["layer_types"]), d_model=d, num_heads=H,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d // H,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=0.0, attn_scale=cfg["attention_multiplier"],
        num_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"], moe_d_ff=cfg["intermediate_size"],
        num_shared_experts=1, shared_d_ff=cfg["shared_intermediate_size"],
        experts_held=cfg["num_local_experts"],
        expert_offset=cfg.get("held_expert_offset", 0), moe_impl="dropless",
        ssm_state=cfg["mamba_d_state"], ssm_headdim=cfg["mamba_d_head"],
        ssm_expand=cfg["mamba_expand"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"], ssm_chunk=cfg["mamba_chunk_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=cfg["residual_multiplier"],
        logit_scale=1.0 / cfg["logits_scaling"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["compute_dtype"], param_dtype=cfg["weights_dtype"],
        scan_layers=False, remat="none")


class HybridChat(Chat):
    """``served_lm.Chat`` on a hybrid model: the same server, slots,
    prefill and step; the step occupancies are kept for the counters."""

    def __init__(self, mcfg, mix: dict, params):
        import jax

        from repro.core import TDG
        from repro.launch.serve import prefill_jit
        from repro.serving import RegionServer
        from repro.training import make_serve_step

        self.mcfg = mcfg
        self.mix = mix
        self.params = params
        self.max_len = mix["max_len"]
        self.prefill = prefill_jit
        self.slots = mix["slots"]
        self.server = RegionServer(max_batch=self.slots,
                                   max_wait_ms=mix["max_wait_ms"],
                                   name="bench-hybrid", adaptive=False)
        decode = make_serve_step(mcfg)
        for i in range(self.slots):
            tdg = TDG(f"decode[{i}]")
            tdg.add_task(decode, ins=["params", "tokens", "pos", "caches"],
                         outs=["next", "caches"], name="decode")
            self.server.register_tenant(f"slot{i}", tdg,
                                        outputs=("next", "caches"))
        self._jax = jax
        self.occupancy: dict[int, int] = {}
        on_batch = self.server.metrics.on_batch

        def count(occupancy: int, coalesced: bool = True) -> None:
            self.occupancy[occupancy] = self.occupancy.get(occupancy, 0) + 1
            on_batch(occupancy, coalesced)

        self.server.metrics.on_batch = count


def state_bytes(mcfg, max_len: int) -> dict[str, int]:
    """Bytes of one member's decode state, by kind: SSD and conv state on
    the Mamba-2 layers, K/V (and slot positions) on the attention layers."""
    import jax

    from repro.models.model import init_caches

    caches = jax.eval_shape(lambda: init_caches(mcfg, 1, max_len))
    out = {"ssd": 0, "conv": 0, "kv": 0}
    for layer in caches:
        for kind, leaves in (("ssd", [layer.get("ssm", {}).get("ssd")]),
                             ("conv", [layer.get("ssm", {}).get("conv")]),
                             ("kv", list(layer.get("attn", {}).values()))):
            out[kind] += sum(math.prod(x.shape) * x.dtype.itemsize
                             for x in leaves if x is not None)
    return out


def _e2e(records: list, cfg: dict, t0: float, t1: float) -> tuple[dict, dict]:
    ttft, itl = [], []
    tokens = prompt_tok = decode_tok = 0
    flops = 0.0
    for r in records:
        ttft.append(r.times[0] - r.due if r.times else math.inf)
        inside = [t for t in r.times if t <= t1]
        tokens += len(inside)
        itl.extend(b - a for a, b in zip(inside, inside[1:]))
        if r.failed:
            itl.append(math.inf)
        if inside:
            prompt_tok += len(r.req.prompt)
            decode_tok += len(inside) - 1
            flops += work_hybrid.request_flops(cfg, len(r.req.prompt),
                                               len(inside))
    window = t1 - t0
    spans = [(r.times[0], r.times[-1]) for r in records if r.times]
    e2e = {"gen_tokens_per_s": tokens / window,
           "itl_p95_ms": nearest_rank(itl, 95) * 1e3}
    counters = {"tokens": tokens, "prompt_tokens": prompt_tok,
                "decode_tokens": decode_tok, "model_flops": flops,
                "ttft_p50_ms": nearest_rank(ttft, 50) * 1e3,
                "ttft_p95_ms": nearest_rank(ttft, 95) * 1e3,
                "itl_p50_ms": nearest_rank(itl, 50) * 1e3,
                "requests": len(records),
                "decoding_max": max((sum(a <= s <= b for a, b in spans)
                                     for s, _ in spans), default=0),
                "finished": sum(r.finished for r in records)}
    return e2e, counters


def reference_gaps(weights, cfg: dict, mix: dict, samples: list,
                   control: bool = False) -> dict[str, float]:
    """Widest gap of a served token below the f32 reference's best logit
    (and, with ``control``, the same for the fp8 reference's first choice)."""
    import jax.numpy as jnp

    tokens, rows, served = qwen2.sequences(
        [(list(r.req.prompt), r.tokens) for r in samples],
        mix["check_batch"], mix["max_len"])
    rows, served, k = qwen2.pad_rows(rows, served, mix["check_rows"])
    ref = granite_hybrid.logits_at(weights, cfg, tokens, rows)
    out = {"served_logit_gap": float(
        qwen2.served_gap(ref, jnp.asarray(served))[:k].max()),
        "compared_tokens": k}
    if control:
        ctl = granite_hybrid.logits_at(weights, cfg, tokens, rows, fp8=True)
        out["control_logit_gap"] = float(qwen2.control_gap(ref, ctl)[:k].max())
    return out


def serve_window(ctx: harness.RunContext, mcfg, weights) -> tuple:
    """Warm, then serve the mix's requests through one measured window.

    Returns (records, window, end-to-end metrics, counters, peak bytes).
    Step occupancies are taken when the window closes: a step still in
    flight then has its device time cut off by the window."""
    import jax

    cfg, mix = ctx.config, ctx.mix
    requests = ctx.generator.make(cfg, mix, ctx.seed, ctx.seconds)
    chat = HybridChat(mcfg, mix, weights)
    _check_layout(chat, weights)
    chat.warm(ctx.log, most=len(requests))
    member = state_bytes(mcfg, mix["max_len"])
    records = [Record(req=r, due=0.0) for r in requests]
    sm = chat.server.metrics

    def snapshot():
        return sm.batches, sm.occupancy_sum, dict(chat.occupancy)

    before, ends = snapshot(), []
    held = [_bytes_in_use(jax.devices())]
    with ctx.window() as win:
        mark_end = win.mark_end

        def close_window() -> None:
            if not ends:
                ends.append(snapshot())
            mark_end()

        win.mark_end = close_window
        loop = _serve(chat, records, win, ctx.seconds, ctx.log)
        held.append(_bytes_in_use(jax.devices()))
    after = ends[0]
    peak = harness.memory_peak_bytes(jax.devices())
    e2e, counters = _e2e(records, cfg, win.t0, win.t1)
    counters.update(loop)
    counters["batches"] = after[0] - before[0]
    counters["occupancy_sum"] = after[1] - before[1]
    counters["step_occupancy"] = {
        k: n - before[2].get(k, 0) for k, n in after[2].items()
        if n > before[2].get(k, 0)}
    counters["rows"] = counters["prompt_tokens"] + counters["decode_tokens"]
    counters["state_bytes_member"] = sum(member.values())
    counters.update({f"state_bytes_{k}": v for k, v in member.items()})
    counters["bytes_in_use_open"], counters["bytes_in_use_close"] = held
    chat.close()
    del chat
    gc.collect()
    return records, win, e2e, counters, peak


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax

    cfg, mix = ctx.config, ctx.mix
    mcfg = model_config(cfg)            # fails at once without the program's support
    weights = granite_hybrid.make_weights(
        cfg, jax_key(ctx.seed), dtype=jax.numpy.dtype(cfg["weights_dtype"]))
    records, win, e2e, counters, peak = serve_window(ctx, mcfg, weights)
    samples = _sample(records, mix, ctx.seed)
    if samples:
        numbers = reference_gaps(weights, cfg, mix, samples)
        counters["compared_tokens"] = numbers.pop("compared_tokens")
        value = numbers["served_logit_gap"]
    else:
        value = math.inf                 # nothing finished: nothing correct
    ctx.log(f"compared {counters.get('compared_tokens', 0)} served tokens "
            f"of {len(samples)} requests")
    failed = sum(r.failed for r in records)
    return harness.RunResult(
        attempted=len(records), failed=failed, end_to_end=e2e,
        checks=[harness.Check("served_logit_gap", value,
                              cfg["limits"]["served_logit_gap"])],
        counters=counters, window=win, memory_peak_bytes=peak)

