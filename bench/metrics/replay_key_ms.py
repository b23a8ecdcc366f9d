"""Mean ``taskgraph.replay.key`` span per window replay, in ms: the replay
cache key (buffer signature, kernel mode, mesh, plan) and its lookup."""
from bench.lib import spans


def read(ctx):
    return spans.child_ms(spans.ring(), ctx.counters.get("replays"),
                          "taskgraph.replay.key")
