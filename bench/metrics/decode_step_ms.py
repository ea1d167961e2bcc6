"""Device time of one execution of the decode program, averaged over the
executions inside the trace."""

DECODE = ("decode_fn",)


def read(ctx):
    mods = ctx.modules(*DECODE)
    return sum(m.end - m.start for m in mods) / len(mods) / 1e6 if mods else None
