"""The held experts' GEMMs' share of their roofline: the least time of
their expected work (``lib.work_hybrid``: the window's prompt tokens, and
each decode step's members) over the summed device time of the ragged-dot
kernels in the trace (``ragged-dot``: the GEMMs and their group metadata).
The trace names ops, not the ``moe.experts`` scope they run under."""
from bench.lib import work_hybrid


def read(ctx):
    if ctx.trace is None or not ctx.counters.get("rows"):
        return None
    secs, calls = ctx.trace.seconds_matching(r"ragged-dot")
    if not calls or secs <= 0:
        return None
    work = [work_hybrid.expert_prefill_work(
        ctx.config, ctx.counters["prompt_tokens"])]
    work += work_hybrid.expert_decode_work(
        ctx.config, ctx.counters.get("step_occupancy", {}))
    return 100.0 * work_hybrid.least_seconds(work, ctx.peaks) / secs
