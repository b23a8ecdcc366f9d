"""Mean ``taskgraph.replay.wait`` span per window replay, in ms: waiting on
the replay's outputs (``block_until_ready``)."""
from bench.lib import spans


def read(ctx):
    return spans.child_ms(spans.ring(), ctx.counters.get("replays"),
                          "taskgraph.replay.wait")
