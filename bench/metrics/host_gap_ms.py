"""Host time per engine step: the part of each ``bench.step`` span in which
no device operation ran, averaged over the steps inside the trace."""

from bench.trace import union


def read(ctx):
    ops = [(o.start, o.end) for o in ctx.trace.ops]
    gaps = [(sp.end - sp.start) - union(ops, sp.start, sp.end) / max(1, ctx.trace.n_devices)
            for sp in ctx.step_spans.values()]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
