"""MB that the fused classes of one replay stack in and slice out, counted
from the region's fusion plan and its slots' shapes (``lib.work``)."""


def read(ctx):
    b = ctx.counters.get("stacked_bytes")
    return None if b is None else b / 1e6
