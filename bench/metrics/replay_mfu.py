"""Whole replays' share of the chip's bf16 peak: the algorithm's n^3/3
operations per replay, times replays, over the window."""


def read(ctx):
    n, flops = ctx.counters.get("replays"), ctx.counters.get("flops_per_replay")
    if not n or flops is None:
        return None
    return 100.0 * n * flops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
