"""Device time of the admission programs (prefill chunks, scratch reset,
block-table writes, block packing, copy-on-write) per 1000 prompt tokens
installed in the steps inside the trace."""

ADMISSION = ("chunk_fn", "paged_store_row_blocks", "paged_set_row", "paged_copy_block", "_lambda")


def read(ctx):
    toks = sum(sum(st.prompt_lens) for st in ctx.steps)
    mods = ctx.modules(*ADMISSION)
    if not toks or not mods:
        return None
    return sum(m.end - m.start for m in mods) / 1e6 / toks * 1000.0
