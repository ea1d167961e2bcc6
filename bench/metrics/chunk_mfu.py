"""The prefill chunk program's share of its roofline: the least time for the
work its chunks carried over the summed device time of the ``jit_chunk_fn``
executions, both over the steps inside the trace.

Each chunk is read from the engine's ``serve.lane.chunk`` span: ``start``
and ``filled`` are the prompt tokens in the scratch before and after it, so
its own tokens are ``[start, filled)`` and each attends causally over the
keys before it.  Its work is those tokens through the body, their causal
attention and the output head once; padding of a last chunk to the chunk's
width is waste, not work.  Its bytes are the weights and the K/V of the
context up to ``filled``.  Its least time is the larger of the operations
over peak FLOP/s and the bytes over HBM bandwidth.  A trace whose chunk
spans carry no ``start`` gives None.
"""

from bench import cost
from bench.engine_spans import spans

CHUNK = "chunk_fn"


def chunk_cost(m: dict, start: int, filled: int) -> tuple[float, float]:
    """(operations, bytes) of one chunk of prompt tokens ``[start, filled)``."""
    n = filled - start
    keys = n * start + n * (n + 1) // 2
    flops = 2 * cost.body_params(m) * n + cost.attn_flops(m, keys) + 2 * cost.head_params(m)
    nbytes = cost.weight_bytes(m) + cost.kv_bytes_per_token(m) * filled
    return float(flops), float(nbytes)


def read(ctx):
    chunks = [s for s in ctx.in_steps(spans(ctx))
              if s.name == "serve.lane.chunk" and "start" in s.stats]
    mods = ctx.modules(CHUNK)
    t = sum(mod.end - mod.start for mod in mods) / 1e9
    if not chunks or not t:
        return None
    least = sum(cost.least_time(*chunk_cost(ctx.model, int(s.stats["start"]),
                                            int(s.stats["filled"])), ctx.peak)[0]
                for s in chunks)
    return 100.0 * least / t
