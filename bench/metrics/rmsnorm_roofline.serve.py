"""The ``rmsnorm`` Pallas kernel's share of its roofline: the least time its
rows need (bytes over HBM bandwidth, or operations over the bf16 peak,
whichever is larger) over its summed device time in the trace."""
from bench.lib import work


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("rows"):
        return None
    secs, calls = ctx.trace.seconds_matching(r"rmsnorm")
    if not calls or secs <= 0:
        return None
    rows = ctx.counters["rows"]
    least = max(work.rmsnorm_bytes(ctx.config, rows)
                / ctx.peaks["hbm_bytes_per_s"],
                work.rmsnorm_flops(ctx.config, rows)
                / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
