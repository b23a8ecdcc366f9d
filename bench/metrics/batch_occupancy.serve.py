"""Mean members per server step in the window (``ServerMetrics``'s
``occupancy_sum`` over ``batches``, both differenced across the window)."""


def read(ctx):
    batches = ctx.counters.get("batches")
    if not batches:
        return None
    return ctx.counters["occupancy_sum"] / batches
