"""Model operations that the window's prompt and generated tokens need
(matrix products plus attention at each token's position, ``lib.work``),
over the window and the chip's bf16 peak."""


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if not flops:
        return None
    return 100.0 * flops / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
