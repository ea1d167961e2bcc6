"""Host time per engine step spent emitting: the part of the engine's
``serve.emit`` spans (token callbacks, finishing and eviction) in which no
device operation ran, per step inside the trace."""

from bench.engine_spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "serve.emit")
