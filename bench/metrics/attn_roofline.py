"""Paged decode attention's share of its roofline: the least time for the
operations and bytes its live rows need (every live key and value read
once, bench/cost.py) over the kernel's summed device time, for the steps
inside the trace.  The kernel is found as the trace names it today: the
Pallas custom call inside the decode program, its only one."""

from bench import cost
from bench.trace import KERNEL

DECODE = "decode_fn"


def read(ctx):
    ops = ctx.ops(DECODE, KERNEL)
    t = sum(o.end - o.start for o in ops) / 1e9
    if not ops or not t:
        return None
    least = sum(cost.least_time(*cost.decode_attention(ctx.model, st.decode_lens), ctx.peak)[0]
                for st in ctx.steps if st.decode_lens)
    return 100.0 * least / t
