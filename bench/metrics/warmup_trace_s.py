"""The last ``taskgraph.warmup.trace`` span, in s: tracing the fused replay
program ahead of time, wave fusion and the cost model's probe compiles
included."""
from bench.lib import spans


def read(ctx):
    return spans.last_s(spans.ring(), "taskgraph.warmup.trace")
