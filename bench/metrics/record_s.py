"""The last ``taskgraph.record`` span (the eager recording run), in s."""
from bench.lib import spans


def read(ctx):
    return spans.last_s(spans.ring(), "taskgraph.record")
