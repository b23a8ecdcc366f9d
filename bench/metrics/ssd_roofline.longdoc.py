"""The SSD chunk kernel's share of its roofline: the least time its work
over the window's prompt tokens needs (``lib.work_hybrid``: operations over
the bf16 peak or bytes over HBM bandwidth, whichever is larger) over the
summed device time of the ``ssd_intra_chunk`` Pallas kernel in the trace."""
from bench.lib import work_hybrid


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("prompt_tokens"):
        return None
    secs, calls = ctx.trace.seconds_matching(r"ssd_intra_chunk")
    if not calls or secs <= 0:
        return None
    work = work_hybrid.ssd_kernel_work(ctx.config, ctx.counters["prompt_tokens"])
    return 100.0 * work_hybrid.least_seconds([work], ctx.peaks) / secs
