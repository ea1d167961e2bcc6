"""Ragged flash-decoding Pallas kernel (single-query decode attention).

Flash-decoding is the FlashAttention online-softmax recurrence split along
the KV axis — the paper's blocked-loop-nest story applied to the serve hot
loop.  One query token per (slot, kv head) attends over a ragged prefix of
the slot's KV cache:

  * grid ``(batch, kv_splits)``: rows are independent; the KV split axis
    is innermost and sequential, so the online-softmax partials (running
    max / normalizer / fp32 accumulator, one set per kv head) live in VMEM
    scratch and are combined across splits without materializing
    per-split outputs.
  * per-row KV **lengths are a scalar-prefetch operand** (SMEM, available
    before the body runs): a traced ``(B,)`` int32, so lengths changing
    every decode step never recompiles, and the k/v index maps alias every
    block past ``ceil(len/bk)`` to the last live block — consecutive equal
    block indices elide the HBM->VMEM copy, so each slot only *reads*
    ``ceil(len/bk)`` KV blocks.  Dead blocks also skip compute via
    ``pl.when``.
  * GQA is resolved **inside** the kernel: a K/V tile is ``(bk, KV, d)``
    — every kv head of one split — and a static loop over heads scores
    each head's ``(G, d)`` query group against its own ``(bk, d)`` slice,
    so KV is fetched once and never broadcast G-fold beforehand.  Tiling
    all heads at once is also what the TPU compiler demands: a block's
    last two dims must be (8, 128)-divisible or equal to the array's, and
    ``(1, d)`` against ``(KV, d)`` is neither.

k/v come in the serve engine's native cache layout ``(B, S, KV, d)`` so the
donated decode loop hands the ring buffers to the kernel with zero copies.

Masking contract: a row's live keys are exactly cache slots
``[0, lengths[b])``, with ``lengths`` clamped to ``[1, S]`` — the serve
loop always scatters the current token before attending, so a live row has
at least one key (length 0 is NOT a fully-masked row here; the dense ref
is the place that models it).  The serve ring invariant (``slot(pos) = pos % size``
with ``size <= window``) makes that single ragged bound equivalent to the
causal + sliding-window + empty-slot mask recipe of ``arch.attention`` —
see ``arch/attention.attend``'s decode dispatch for the derivation.

:func:`decode_attention_xla` is the kernel's jnp twin for CPU serving: the
same blocked online-softmax recurrence, vectorized over rows, with a
``lax.while_loop`` whose trip count is ``ceil(max(lengths)/bk)`` — decode
step time scales with the *live* length, not ``max_len``.  Contributions of
a fully-masked block are exactly zero (``exp(NEG_INF - m)`` underflows and
the correction factor is ``exp(0)``), so padding rows to the batch max is
bitwise-neutral, which keeps batched serving bitwise-equal to solo runs.

Paged variants (:func:`flash_decode_paged_pallas`,
:func:`decode_attention_paged_xla`): k/v live in a shared **block pool**
``(num_blocks, block_size, KV, d)`` instead of a dense per-slot axis, and
each row carries a **block table** ``(B, max_blocks)`` mapping its logical
block ``j`` to a physical pool block.  The grid stays
``(batch, kv_splits)`` with ``kv_splits == max_blocks``; the
only change is that the k/v index maps go through the table — a second
scalar-prefetch operand — so block-table *contents* never recompile, and
dead splits alias to the row's last live **physical** block exactly like
the dense variant.  Because the KV split boundary is the block boundary,
the paged recurrence visits the same logical key ranges in the same order
as the dense kernel at ``bk == block_size``: outputs are bitwise equal,
which is what lets the contiguous serve engine act as the paged engine's
differential oracle.  An optional ``window`` additionally masks
``k_idx <= length - 1 - window`` for sliding-window rows (position ==
logical index in the paged layout; there is no ring).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import (
    NEG_INF,
    finalize_out,
    last_live_block,
    reset_carry,
)


def _live_keys(k_idx, length, window):
    """Live-key predicate of one decode row: logical index < length, plus
    the paged kernel's optional sliding window against the query position
    ``length - 1`` (logical index == absolute position in the paged
    layout)."""
    ok = k_idx < length
    if window is not None:
        ok &= k_idx > length - 1 - window
    return ok


def _decode_step(
    length,                       # live keys of this row (SMEM scalar)
    q_ref,                        # (1, KV, G, d)
    k_ref,                        # (1, bk, KV, d)
    v_ref,                        # (1, bk, KV, d)
    o_ref,                        # (1, KV, G, d)
    m_ref, l_ref, acc_ref,        # VMEM scratch: (KV, G), (KV, G), (KV, G, d) fp32
    *, bk: int, n_k: int, scale: float, window: int | None,
):
    """One KV split of one row, shared by the dense and paged kernels.  The
    K/V block carries every kv head (its last two dims are the array's
    ``(KV, d)``, which is what the TPU block-shape rule accepts for any KV);
    a static loop over heads runs the online-softmax update per head."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        reset_carry(m_ref, l_ref, acc_ref)

    @pl.when(j * bk < length)
    def _live():
        for h in range(q_ref.shape[1]):     # static: one pass per kv head
            q = q_ref[0, h]                   # (G, d)
            k = k_ref[0, :, h, :]             # (bk, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                         # (G, bk)
            k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_live_keys(k_idx, length, window), s, NEG_INF)

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, :, h, :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )

    @pl.when(j == n_k - 1)
    def _store():
        finalize_out(o_ref, l_ref, acc_ref)


def _decode_scratch(KV: int, G: int, d: int) -> list:
    return [
        pltpu.VMEM((KV, G), jnp.float32),
        pltpu.VMEM((KV, G), jnp.float32),
        pltpu.VMEM((KV, G, d), jnp.float32),
    ]


def _decode_kernel(lens_ref, *refs, **kw):
    # lens_ref: SMEM (B,) int32 scalar-prefetch
    _decode_step(lens_ref[pl.program_id(0)], *refs, window=None, **kw)


def flash_decode_pallas(
    q: jax.Array,         # (B, KV, G, d) one query token per (slot, head)
    k: jax.Array,         # (B, S, KV, d) native cache layout
    v: jax.Array,         # (B, S, KV, d)
    lengths: jax.Array,   # (B,) int32 live KV slots per row (traced)
    *,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, KV, G, d = q.shape
    S = k.shape[1]
    assert S % bk == 0, (S, bk)
    n_k = S // bk
    scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, S)

    def kv_block(b, j, lens):
        return (b, jnp.minimum(j, last_live_block(lens[b], bk)), 0, 0)

    def row(b, j, lens):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((1, KV, G, d), row),
            pl.BlockSpec((1, bk, KV, d), kv_block),
            pl.BlockSpec((1, bk, KV, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, d), row),
        scratch_shapes=_decode_scratch(KV, G, d),
    )
    kern = functools.partial(
        _decode_kernel, bk=bk, n_k=n_k, scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
    )(lengths, q, k, v)


def decode_attention_xla(
    q: jax.Array,         # (B, KV, G, d)
    k: jax.Array,         # (B, S, KV, d)
    v: jax.Array,         # (B, S, KV, d)
    lengths: jax.Array,   # (B,) int32
    *,
    bk: int = 128,
) -> jax.Array:
    """The kernel's jnp twin: same blocked recurrence, rows vectorized,
    while-loop trip count = the batch's deepest live split."""
    B, KV, G, d = q.shape
    S = k.shape[1]
    assert S % bk == 0, (S, bk)
    scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, S)
    n_live = jnp.max((lengths + bk - 1) // bk)

    def body(state):
        j, m, l, acc = state
        kb = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
        s = jnp.einsum(
            "bhgd,bshd->bhgs", q, kb, preferred_element_type=jnp.float32
        ) * scale                                       # (B, KV, G, bk)
        k_idx = j * bk + jnp.arange(bk, dtype=jnp.int32)
        live = k_idx[None, :] < lengths[:, None]        # (B, bk)
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgs,bshd->bhgd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return j + 1, m_new, l, acc

    state = (
        jnp.int32(0),
        jnp.full((B, KV, G), NEG_INF, jnp.float32),
        jnp.zeros((B, KV, G), jnp.float32),
        jnp.zeros((B, KV, G, d), jnp.float32),
    )
    _, _, l, acc = jax.lax.while_loop(lambda st: st[0] < n_live, body, state)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------------------ paged variants


def _paged_decode_kernel(lens_ref, table_ref, *refs, **kw):
    # lens_ref: SMEM (B,) int32; table_ref: SMEM (B, n_blk) int32 — both
    # scalar-prefetch; the table is only read by the index maps
    _decode_step(lens_ref[pl.program_id(0)], *refs, **kw)


def flash_decode_paged_pallas(
    q: jax.Array,         # (B, KV, G, d) one query token per (row, head)
    kpool: jax.Array,     # (num_blocks, bs, KV, d) shared block pool
    vpool: jax.Array,     # (num_blocks, bs, KV, d)
    tables: jax.Array,    # (B, n_blk) int32 logical -> physical block
    lengths: jax.Array,   # (B,) int32 live tokens per row (traced)
    *,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, KV, G, d = q.shape
    bs = kpool.shape[1]
    n_blk = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, n_blk * bs)
    tables = tables.astype(jnp.int32)

    def kv_block(b, j, lens, tabs):
        last = last_live_block(lens[b], bs)
        return (tabs[b, jnp.minimum(j, last)], 0, 0, 0)

    def row(b, j, lens, tabs):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blk),
        in_specs=[
            pl.BlockSpec((1, KV, G, d), row),
            pl.BlockSpec((1, bs, KV, d), kv_block),
            pl.BlockSpec((1, bs, KV, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, d), row),
        scratch_shapes=_decode_scratch(KV, G, d),
    )
    kern = functools.partial(
        _paged_decode_kernel,
        bk=bs, n_k=n_blk, scale=scale, window=window,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
    )(lengths, tables, q, kpool, vpool)


def decode_attention_paged_xla(
    q: jax.Array,         # (B, KV, G, d)
    kpool: jax.Array,     # (num_blocks, bs, KV, d)
    vpool: jax.Array,     # (num_blocks, bs, KV, d)
    tables: jax.Array,    # (B, n_blk) int32
    lengths: jax.Array,   # (B,) int32
    *,
    window: int | None = None,
) -> jax.Array:
    """Gather-based jnp twin of the paged kernel: the same blocked
    recurrence as :func:`decode_attention_xla` with the KV block fetched
    through the block table (one ``(B,)`` gather per live split) instead of
    a dynamic slice.  At ``bk == block_size`` the two twins are bitwise
    equal on equal logical contents — the paged serve engine's differential
    oracle rests on this."""
    B, KV, G, d = q.shape
    bs = kpool.shape[1]
    scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, tables.shape[1] * bs)
    tables = tables.astype(jnp.int32)
    n_live = jnp.max((lengths + bs - 1) // bs)

    def body(state):
        j, m, l, acc = state
        phys = jax.lax.dynamic_slice_in_dim(tables, j, 1, axis=1)[:, 0]
        kb = jnp.take(kpool, phys, axis=0)              # (B, bs, KV, d)
        vb = jnp.take(vpool, phys, axis=0)
        s = jnp.einsum(
            "bhgd,bshd->bhgs", q, kb, preferred_element_type=jnp.float32
        ) * scale                                       # (B, KV, G, bs)
        k_idx = j * bs + jnp.arange(bs, dtype=jnp.int32)
        live = _live_keys(k_idx[None, :], lengths[:, None], window)
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgs,bshd->bhgd", p.astype(vpool.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return j + 1, m_new, l, acc

    state = (
        jnp.int32(0),
        jnp.full((B, KV, G), NEG_INF, jnp.float32),
        jnp.zeros((B, KV, G), jnp.float32),
        jnp.zeros((B, KV, G, d), jnp.float32),
    )
    _, _, l, acc = jax.lax.while_loop(lambda st: st[0] < n_live, body, state)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)
