"""Back-to-back replays of one recorded region: the work of every replay is
the same, so the mix only sizes the tiles and the number in flight."""
from __future__ import annotations


def make(config: dict, mix: dict, seed: int, seconds: float) -> dict:
    n, nb = config["n"], mix["nb"]
    if n % nb:
        raise ValueError(f"n={n} is not a multiple of nb={nb}")
    if mix.get("in_flight", 1) != 1:
        raise ValueError("the replay mix keeps exactly one replay in flight")
    return {"n": n, "nb": nb}
