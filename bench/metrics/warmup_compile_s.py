"""The last ``taskgraph.warmup.compile`` span, in s: compiling the fused
replay program, or loading it from the persistent compile cache."""
from bench.lib import spans


def read(ctx):
    return spans.last_s(spans.ring(), "taskgraph.warmup.compile")
