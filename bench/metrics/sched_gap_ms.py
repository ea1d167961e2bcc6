"""Host time per engine step spent scheduling: the part of the engine's
``serve.schedule`` spans (deadlines, preemption, admission and the prefill
lane, copy-on-write, building and uploading the decode inputs) in which no
device operation ran, per step inside the trace."""

from bench.engine_spans import idle_ms_per_step


def read(ctx):
    return idle_ms_per_step(ctx, "serve.schedule")
