"""Open-loop chat traffic: requests arrive on a fixed schedule, whether or
not earlier ones are done, and wait for one of the server's slots.

The schedule (prompt lengths, output lengths and arrival gaps: stratified
quantiles of the mix's distributions) is a function of the mix alone, in one
fixed order; the seed draws the token ids. Below the knee a 30 s window holds
tens of requests, and their order decides which overlap and which are still
in flight at the close, so an order drawn from the seed would change the
amount of work from seed to seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

from bench.lib.seeds import np_rng

@dataclasses.dataclass
class Request:
    rid: int
    arrival_s: float          # offset from the window's start
    prompt: np.ndarray        # int32 token ids
    out_len: int              # tokens to generate, the first from prefill


def _counts(n: int, probs: list[float]) -> list[int]:
    """Largest-remainder split of n into shares ``probs``."""
    raw = [p * n for p in probs]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(probs)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def schedule(mix: dict, seconds: float):
    """Prompt lengths, output lengths and arrival offsets of the window's
    requests, the same for every seed."""
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    lengths = np.repeat(mix["prompt_lengths"], _counts(n, mix["prompt_probs"]))
    out = np.exp(math.log(mix["output_median"])
                 + mix["output_sigma"] * np.array(
                     [NormalDist().inv_cdf(x) for x in q]))
    out = np.clip(np.round(out), mix["output_min"], mix["output_max"]).astype(int)
    gaps = -np.log(1.0 - q) / rate           # exponential quantiles
    # scale so that the last arrival lands inside the window
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    order = np_rng(0)
    lengths, out, gaps = (order.permutation(x) for x in (lengths, out, gaps))
    if max(lengths) + mix["output_max"] > mix["max_len"]:
        raise ValueError("the mix's longest request exceeds its max_len")
    return lengths, out, np.cumsum(gaps) - gaps[0]


def make(config: dict, mix: dict, seed: int, seconds: float) -> list[Request]:
    lengths, out, arrivals = schedule(mix, seconds)
    rng = np_rng(seed)
    vocab = config["vocab_size"]
    return [Request(rid=i, arrival_s=float(arrivals[i]),
                    prompt=rng.integers(0, vocab, int(lengths[i]),
                                        dtype=np.int32),
                    out_len=int(out[i]))
            for i in range(len(lengths))]
