"""Window time per replay outside ``TaskGraphRegion.replay``, in ms:
``window_s / replays`` less the mean ``taskgraph.replay`` span."""
from bench.lib import spans


def read(ctx):
    return spans.outside_ms(spans.ring(), ctx.counters.get("replays"),
                            ctx.window_s)
