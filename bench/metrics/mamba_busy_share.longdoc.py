"""Share of the device's busy time spent in the Mamba-2 mixers' chunk-scan
kernel (``ssd_intra_chunk``). The trace names ops, not the ``mamba2`` scope
they run under, so the mixers' projections, conv and decode recurrence
are not in it."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    secs, calls = ctx.trace.seconds_matching(r"ssd_intra_chunk")
    if not calls:
        return None
    return 100.0 * secs / ctx.trace.busy_s
