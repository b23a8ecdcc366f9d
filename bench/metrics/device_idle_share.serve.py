"""Share of the serving window in which no operation ran on the device."""
from bench.lib.trace import idle_percent


def read(ctx):
    return idle_percent(ctx.trace)
