"""Host time per engine step between the decode dispatch and its tokens on
the host: the part of the engine's ``serve.decode`` spans in which no
device operation ran (launch, upload and sync latency), per step inside
the trace."""

from bench.engine_spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "serve.decode")
