"""Share of the traced window in which no operation ran on the device."""

from bench.trace import busy_ns


def read(ctx):
    span = ctx.hi - ctx.lo
    return 100.0 * (1.0 - busy_ns(ctx.trace, ctx.lo, ctx.hi) / span) if span > 0 else None
