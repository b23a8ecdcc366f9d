"""Mean device time of one prefill program in the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.seconds_matching(r"prefill", modules=True)
    return secs / calls * 1e3 if calls else None
