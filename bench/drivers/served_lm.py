"""Driver for a served language model: requests arrive on the mix's open-loop
schedule and each takes one of the server's slots, as ``launch/serve.py
--server`` drives a ``RegionServer``.

A slot is a server tenant with batch 1. A request is prefilled with
``launch.serve.prefill_jit`` (its first token), then decoded one
``RegionServer.serve`` step per token; structurally identical steps of busy
slots coalesce into one batched replay. A request that finds every slot
busy waits in a FIFO queue, and its time to first token counts the wait.

Set-up makes the weights from the seed and warms every program the window
can run: a prefill per prompt length, the lone decode step and the batched
step at every occupancy bucket up to the most requests that can decode at
once (the window's request count, at most the slot count).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue
import threading
import time

import numpy as np

from bench.lib import harness, work
from bench.lib.seeds import jax_key, np_rng
from bench.reference import qwen2

#: A served step may wait on a prefill and a full batch ahead of it.
STEP_TIMEOUT_S = 120.0


@dataclasses.dataclass
class Record:
    """What the client saw of one request: when each token came, and which."""

    req: object
    due: float                       # absolute perf_counter arrival time
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    failed: bool = False
    finished: bool = False


def model_config(cfg: dict):
    """The program's model config for a Qwen2-style configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["hidden_act"] != "silu" or cfg.get("use_sliding_window"):
        raise ValueError("the served_lm driver runs full-attention SwiGLU "
                         "decoders only")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["compute_dtype"],
        param_dtype=cfg["weights_dtype"])


def nearest_rank(values: list[float], q: float) -> float:
    """The q-th percentile by nearest rank (inf counts as the largest)."""
    if not values:
        return math.nan
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


class Chat:
    """One server, its slots, and the prefill/decode path of a request."""

    def __init__(self, cfg: dict, mix: dict, params):
        import jax

        from repro.core import TDG
        from repro.launch.serve import prefill_jit
        from repro.serving import RegionServer
        from repro.training import make_serve_step

        self.mcfg = model_config(cfg)
        self.mix = mix
        self.params = params
        self.max_len = mix["max_len"]
        self.prefill = prefill_jit
        self.slots = mix["slots"]
        # The static power-of-two ladder: the adaptive tuner would refit its
        # buckets from live occupancies and compile inside the window.
        self.server = RegionServer(max_batch=self.slots,
                                   max_wait_ms=mix["max_wait_ms"],
                                   name="bench-chat", adaptive=False)
        decode = make_serve_step(self.mcfg)
        for i in range(self.slots):
            tdg = TDG(f"decode[{i}]")
            tdg.add_task(decode, ins=["params", "tokens", "pos", "caches"],
                         outs=["next", "caches"], name="decode")
            self.server.register_tenant(f"slot{i}", tdg,
                                        outputs=("next", "caches"))
        self._jax = jax

    def first_token(self, prompt: np.ndarray):
        jnp = self._jax.numpy
        with self._jax.profiler.TraceAnnotation("bench.prefill"):
            logits, caches, pos = self.prefill(
                self.params, self.mcfg, {"tokens": jnp.asarray(prompt)[None]},
                self.max_len)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok, int(tok[0]), caches, pos

    def step(self, slot: int, tok, pos, caches):
        with self._jax.profiler.TraceAnnotation("bench.serve_step"):
            out = self.server.serve(f"slot{slot}", {
                "params": self.params, "tokens": tok[:, None], "pos": pos,
                "caches": caches}, timeout=STEP_TIMEOUT_S)
            tok = out["next"]
            return tok, int(tok[0]), out["caches"], pos + 1

    def warm(self, log, most: int | None = None) -> None:
        """Run every program the window can: each prompt length's prefill
        and decode step, then one coalesced step at every bucket up to
        ``most`` members (the slot count by default)."""
        jnp = self._jax.numpy
        rng = np_rng(0, 1)
        state = None
        for length in sorted(set(self.mix["prompt_lengths"])):
            prompt = rng.integers(0, self.mcfg.vocab_size, length,
                                  dtype=np.int32)
            tok, _, caches, pos = self.first_token(prompt)
            tok, _, caches, pos = self.step(0, tok, pos, caches)
            state = (tok, pos, caches)
        most = min(self.slots, most or self.slots)
        buckets = sorted({self.server.buckets.bucket_for(k)
                          for k in range(2, most + 1)})
        tok, pos, caches = state
        for b in buckets:
            members = [(f"slot{i}", {
                "params": self.params, "tokens": jnp.copy(tok[:, None]),
                "pos": jnp.copy(pos),
                "caches": self._jax.tree_util.tree_map(jnp.copy, caches)})
                for i in range(b)]
            futures = self.server.submit_many(members)
            self._jax.block_until_ready(
                [f.result(timeout=None) for f in futures])
            del members, futures
        m = self.server.metrics.snapshot()
        log(f"warmed prefill x{len(set(self.mix['prompt_lengths']))}, "
            f"decode buckets {buckets}; server batches {m['batches']}, "
            f"occupancy max {m['batch_occupancy_max']}")

    def close(self) -> None:
        self.server.close()


def _serve(chat: Chat, records: list, win, seconds: float, log) -> dict:
    """The open loop: arrivals on schedule, ``slots`` workers, FIFO wait."""
    waiting: queue.Queue = queue.Queue()
    stop = threading.Event()
    late = []

    def worker(slot: int) -> None:
        while True:
            rec = waiting.get()
            if rec is None:
                return
            try:
                tok, tid, caches, pos = chat.first_token(rec.req.prompt)
                rec.times.append(time.perf_counter())
                rec.tokens.append(tid)
                for _ in range(rec.req.out_len - 1):
                    if stop.is_set():
                        break
                    tok, tid, caches, pos = chat.step(slot, tok, pos, caches)
                    rec.times.append(time.perf_counter())
                    rec.tokens.append(tid)
                else:
                    rec.finished = True
            except Exception as e:      # a failed request misses every limit
                rec.failed = True
                log(f"request {rec.req.rid} failed: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(chat.slots)]
    for t in threads:
        t.start()
    t0 = win.t0
    for rec in records:
        rec.due = t0 + rec.req.arrival_s
        delay = rec.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        late.append(time.perf_counter() - rec.due)
        waiting.put(rec)
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    win.mark_end()
    stop.set()                          # in-flight requests stop decoding
    for _ in threads:
        waiting.put(None)               # queued ones still get a first token
    for t in threads:
        t.join(timeout=STEP_TIMEOUT_S * 2)
    return {"generator_late_max_s": max(late, default=0.0),
            "stuck_workers": sum(t.is_alive() for t in threads)}


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
            flops += work.qwen2_request_flops(
                cfg, len(r.req.prompt), len(inside))
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


def _sample(records: list, mix: dict, seed: int) -> list:
    """Finished requests to compare, drawn from the seed, the longest first,
    up to the mix's count of served tokens and the reference's batch."""
    done = [r for r in records if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.req.rid))
    rest = [r for r in done if r is not longest]
    order = np_rng(seed, 2).permutation(len(rest))
    chosen, total = [longest], len(longest.tokens)
    for i in order:
        r = rest[i]
        if total >= mix["check_tokens"] or len(chosen) == mix["check_batch"]:
            break
        if total + len(r.tokens) <= mix["check_rows"]:
            chosen.append(r)
            total += len(r.tokens)
    return chosen


def reference_gaps(weights, cfg: dict, mix: dict, samples: list,
                   control: bool = False) -> dict[str, float]:
    """Widest gap of a served token below the f32 reference's best logit
    (and, with ``control``, the same for the fp8 reference's first choice)."""
    import jax.numpy as jnp

    tokens, rows, served = qwen2.sequences(
        [(list(r.req.prompt), r.tokens) for r in samples],
        mix["check_batch"], mix["max_len"])
    rows, served, k = qwen2.pad_rows(rows, served, mix["check_rows"])
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    ref = qwen2.logits_at(weights, cfg, tokens, rows)
    out = {"served_logit_gap": float(
        qwen2.served_gap(ref, jnp.asarray(served))[:k].max()),
        "compared_tokens": k}
    if control:
        ctl = qwen2.logits_at(weights, cfg, tokens, rows, fp8=True)
        out["control_logit_gap"] = float(qwen2.control_gap(ref, ctl)[:k].max())
    return out


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax

    cfg, mix = ctx.config, ctx.mix
    requests = ctx.generator.make(cfg, mix, ctx.seed, ctx.seconds)
    weights = qwen2.make_weights(cfg, jax_key(ctx.seed),
                                 dtype=jax.numpy.dtype(cfg["weights_dtype"]))
    chat = Chat(cfg, mix, weights)
    _check_layout(chat, weights)
    chat.warm(ctx.log, most=len(requests))
    records = [Record(req=r, due=0.0) for r in requests]
    sm = chat.server.metrics
    before = (sm.batches, sm.occupancy_sum)
    held = [_bytes_in_use(jax.devices())]
    with ctx.window() as win:
        loop = _serve(chat, records, win, ctx.seconds, ctx.log)
        after = (sm.batches, sm.occupancy_sum)
        held.append(_bytes_in_use(jax.devices()))
    peak = harness.memory_peak_bytes(jax.devices())
    e2e, counters = _e2e(records, cfg, win.t0, win.t1)
    counters.update(loop)
    counters["batches"] = after[0] - before[0]
    counters["occupancy_sum"] = after[1] - before[1]
    counters["rows"] = counters["prompt_tokens"] + counters["decode_tokens"]
    counters["bytes_in_use_open"], counters["bytes_in_use_close"] = held
    chat.close()
    del chat
    gc.collect()

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


def _bytes_in_use(devices) -> int:
    """Bytes held now on the fullest device (the peak since start-up is
    ``harness.memory_peak_bytes``; here it is set by the warm-up's largest
    bucket)."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def _check_layout(chat: Chat, weights) -> None:
    """The benchmark's weights must have the program's parameter layout."""
    import jax

    from repro.models import init_params

    want = jax.eval_shape(lambda k: init_params(chat.mcfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype), weights)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(got) \
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise ValueError("the benchmark's weights do not match the program's "
                         "parameter layout")
