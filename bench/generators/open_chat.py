"""Open-loop chat traffic: requests arrive on a fixed schedule, whether or
not earlier ones are done, and wait for one of the server's slots.

Every seed gets the same set of prompt lengths, output lengths and arrival
gaps (stratified quantiles of the mix's distributions), in an order and with
token ids drawn from the seed, so the seed changes the order of the work and
not its amount.
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


def make(config: dict, mix: dict, seed: int, seconds: float) -> list[Request]:
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    rng = np_rng(seed)
    lengths = np.repeat(mix["prompt_lengths"], _counts(n, mix["prompt_probs"]))
    out = np.exp(math.log(mix["output_median"])
                 + mix["output_sigma"] * np.array(
                     [NormalDist().inv_cdf(x) for x in q]))
    out = np.clip(np.round(out), mix["output_min"], mix["output_max"]).astype(int)
    gaps = -np.log(1.0 - q) / rate           # exponential quantiles
    # scale so that the last arrival lands inside the window
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    lengths, out, gaps = (rng.permutation(x) for x in (lengths, out, gaps))
    arrivals = np.cumsum(gaps) - gaps[0]
    vocab = config["vocab_size"]
    if max(lengths) + mix["output_max"] > mix["max_len"]:
        raise ValueError("the mix's longest request exceeds its max_len")
    return [Request(rid=i, arrival_s=float(arrivals[i]),
                    prompt=rng.integers(0, vocab, int(lengths[i]),
                                        dtype=np.int32),
                    out_len=int(out[i]))
            for i in range(n)]
