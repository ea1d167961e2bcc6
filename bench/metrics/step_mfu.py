"""The decode step's share of the chip's peak: its least time (the larger
of its operations over peak FLOP/s and its weights plus live K/V over HBM
bandwidth, from model sizes in bench/cost.py) over the decode program's
measured device time, for the steps inside the trace."""

from bench import cost

DECODE = ("decode_fn",)


def read(ctx):
    mods = ctx.modules(*DECODE)
    t = sum(m.end - m.start for m in mods) / 1e9
    steps = [st for st in ctx.steps if st.decode_lens]
    if not mods or not t or not steps:
        return None
    least = sum(cost.least_time(*cost.decode_step(ctx.model, st.decode_lens), ctx.peak)[0]
                for st in steps)
    return 100.0 * least / t
