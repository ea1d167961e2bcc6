"""Share of the prompt tokens admitted in the window whose blocks the
prefix index aliased, read from the engine's public pool just before the
step that admitted each request."""


def read(ctx):
    recs = [r for r in ctx.records if r.admitted is not None and ctx.opened <= r.admitted < ctx.closed]
    total = sum(len(r.req.prompt) for r in recs)
    return 100.0 * sum(r.hit_tokens for r in recs) / total if total else None
