"""Mean ``taskgraph.replay.dispatch`` span per window replay, in ms: the
compiled program's call, from the buffer dict until it returns."""
from bench.lib import spans


def read(ctx):
    return spans.child_ms(spans.ring(), ctx.counters.get("replays"),
                          "taskgraph.replay.dispatch")
