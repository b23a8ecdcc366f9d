"""Model operations that the window's prompt and generated tokens need
(matrix products plus attention at each token's position, ``lib.work``),
over the time in which an operation ran on the device and the chip's bf16
peak: the served steps' and prefills' share of the peak while they run."""


def read(ctx):
    flops = ctx.counters.get("model_flops")
    if not flops or ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * flops / ctx.trace.busy_s / ctx.peaks["bf16_flops_per_s"]
