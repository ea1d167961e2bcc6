"""Mean time from when a request was due to the start of the step after
which the engine no longer held it waiting (host clock, traced run)."""


def read(ctx):
    waits = [r.admitted - r.due for r in ctx.records
             if ctx.opened <= r.due < ctx.closed and r.admitted is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
