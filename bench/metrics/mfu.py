"""Model FLOP/s utilisation: the model operations of every prompt installed
and every token decoded in the steps inside the trace (bench/cost.py), per
second of the traced window, over the chip's peak FLOP/s."""

from bench import cost


def read(ctx):
    flops = 0.0
    for st in ctx.steps:
        flops += sum(cost.prompt_flops(ctx.model, p) for p in st.prompt_lens)
        flops += sum(cost.output_flops(ctx.model, k) for k in st.decode_lens)
    span = (ctx.hi - ctx.lo) / 1e9
    return 100.0 * flops / span / ctx.peak["flops"] if flops and span else None
