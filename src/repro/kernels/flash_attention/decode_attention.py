"""Ragged flash-decoding Pallas kernel (single-query decode attention).

Flash-decoding is the FlashAttention online-softmax recurrence split along
the KV axis — the paper's blocked-loop-nest story applied to the serve hot
loop.  One query token per (slot, kv head) attends over a ragged prefix of
the slot's KV cache:

  * grid ``(batch, kv_splits)``: rows are independent; the KV split axis
    is innermost and sequential, so the online-softmax partials (running
    max / normalizer / fp32 accumulator, one set per score row) live in VMEM
    scratch and are combined across splits without materializing
    per-split outputs.
  * per-row KV **lengths are a scalar-prefetch operand** (SMEM, available
    before the body runs): a traced ``(B,)`` int32, so lengths changing
    every decode step never recompiles, and the k/v index maps alias every
    block past ``ceil(len/bk)`` to the last live block — consecutive equal
    block indices elide the HBM->VMEM copy, so each slot only *reads*
    ``ceil(len/bk)`` KV blocks.  Dead blocks also skip compute via
    ``pl.when``.
  * GQA is resolved **inside** the kernel, in **one score dot and one
    value dot** a split: a K/V tile is ``(bk, KV * d)`` — every kv head of
    one split, heads side by side in the lanes — and the query is laid out
    block-diagonally, ``(KV * G, KV * d)``, with head ``h``'s ``(G, d)``
    group at rows ``h * G`` and lanes ``h * d`` and zeros elsewhere, once
    a row.  ``qbd @ k.T`` scores every head against its own keys (the
    zeros cancel the others), one online-softmax update runs over the
    ``(KV * G, bk)`` tile, and ``p @ v`` puts each score row's values in its
    head's lanes, where the last split reads them out.  KV is fetched once
    and never broadcast G-fold (see :func:`_decode_step`).

k/v come in the serve engine's cache layout ``(B, S, KV, d)``, which the
kernel views as ``(B, S, KV * d)``.

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
:func:`decode_attention_paged_xla`): k/v live in the serve engine's
stacked **block pools** ``(L, num_blocks, bs // r, r * KV * d)`` — every
layer's pool, packed ``r`` tokens to a row so a row is whole 128-lane tiles
(``serve.kvcache.pack_blocks``) — and each row carries a **block table**
``(B, max_blocks)`` mapping its logical block ``j`` to a physical pool
block.  The grid is one axis over a **work list** of live ``(row, logical
block)`` items (:func:`paged_work_list`), rows in order, and its length is
the list's traced length: a row of ``len`` tokens costs ``ceil(len/bs)``
grid steps, a row that holds no request one, so the grid walks the
blocks the rows hold and not the whole ``(batch, max_blocks)`` rectangle.
The work list, its length, the table and a layer index are
scalar-prefetch operands, so none of them recompiles; the index maps go
from an item to its row and through the table to its physical block.  A
row's items are consecutive, so its carry resets at its first block and
its output is written once, at its last.  Reading a layer by index, the
kernel takes the decode program's pools as they are, with no per-layer
slice.  The body is the dense kernel's with ``r`` token parities: a packed
row holds ``r`` tokens' heads side by side, so the block-diagonal query is
``(r * KV * G, r * KV * d)``, score row ``(t, h, g)`` scores the block's
keys ``t, t + r, ...``, the running max of a ``(h, g)`` is taken over its
``r`` parity rows before exponentiating, and the parity rows' sums are
added at the row's last item.  Because the KV split boundary is the block
boundary, the paged recurrence visits the same logical key ranges in the
same order as the dense kernel at ``bk == block_size``; within a block the
kernel sums the ``r`` token parities separately (so it matches the dense
kernel to rounding), while the XLA twin unpacks the block and is bitwise
equal to the dense twin, which is what lets the contiguous serve engine act
as the paged engine's differential oracle on CPU.  An optional ``window``
additionally masks ``k_idx <= length - 1 - window`` for sliding-window
rows (position == logical index in the paged layout; there is no ring).
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.flash_attention import (
    NEG_INF,
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
    j,                            # which KV split of the row this step reads
    last,                         # this step ends the row: finalize into o_ref
    q_ref,                        # (1, KV, G, d)
    k, v,                         # (bk // r, r * KV * d): r tokens to a row
    o_ref,                        # (1, KV, G, d)
    qbd_ref,                      # VMEM scratch (r * KV * G, r * KV * d), q's dtype
    m_ref, l_ref, acc_ref,        # VMEM scratch: (r*KV*G, 1) x 2, (r*KV*G, r*KV*d) f32
    *, r: int, bk: int, scale: float, window: int | None,
):
    """One KV split of one row, shared by the dense (``r == 1``) and paged
    kernels: every kv head and token parity in one score dot and one value
    dot.  Token ``t`` of the split sits at row ``t // r`` of ``k``/``v``,
    head ``h`` at lanes ``c * d ... + d`` with ``c = (t % r) * KV + h``.

    At the row's first split the query is laid out block-diagonally in
    ``qbd_ref``: score row ``(t, h, g)`` (``c * G + g``) holds ``q[h, g]`` at
    block ``c``'s lanes and zeros elsewhere, and later splits of the row
    reuse it.  ``qbd @ k.T`` is then the ``(r * KV * G, bk // r)`` score tile,
    row ``(t, h, g)`` and column ``p`` scoring key ``j * bk + p * r + t``;
    one online-softmax update runs over it, and ``p @ v`` lands each row's
    values in its own block of lanes (the other blocks are never read).
    The running max is one per ``(h, g)``, taken over its ``r`` parity rows
    before any score is exponentiated, so every parity row shares one
    scale; the normalizer and accumulator are kept per score row, and the
    parity rows are summed once, at the row's last split.  The caller
    names the split ``j`` and whether the row ends here: the dense grid
    walks ``(row, split)``, the paged grid a list of work items."""
    KV, G, d = q_ref.shape[1:]
    n = KV * G                            # score rows of one token parity

    @pl.when(j == 0)
    def _init():
        reset_carry(m_ref, l_ref, acc_ref)
        qbd_ref[...] = jnp.zeros_like(qbd_ref)
        for c in range(r * KV):           # static: one block per (t, h)
            qbd_ref[c * G:(c + 1) * G, c * d:(c + 1) * d] = q_ref[0, c % KV]

    @pl.when(j * bk < length)
    def _live():
        s = jax.lax.dot_general(
            qbd_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                         # (r * KV * G, bk // r)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        parity = sum((row >= t * n).astype(jnp.int32) for t in range(1, r))
        k_idx = j * bk + parity + r * jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(_live_keys(k_idx, length, window), s, NEG_INF)
        m_prev = m_ref[...]
        m_ref[...] = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if r > 1:                         # one max over a (h, g)'s parities
            m = functools.reduce(
                jnp.maximum, [m_ref[t * n:(t + 1) * n] for t in range(r)]
            )
            for t in range(r):
                m_ref[t * n:(t + 1) * n] = m
        m_new = m_ref[...]
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _store():
        for h in range(KV):
            cs = [t * KV + h for t in range(r)]
            l = functools.reduce(
                operator.add, [l_ref[c * G:(c + 1) * G] for c in cs]
            )
            acc = functools.reduce(operator.add, [
                acc_ref[c * G:(c + 1) * G, c * d:(c + 1) * d] for c in cs
            ])
            o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _decode_scratch(q: jax.Array, r: int) -> list:
    _, KV, G, d = q.shape
    n, lanes = r * KV * G, r * KV * d
    return [
        pltpu.VMEM((n, lanes), q.dtype),
        pltpu.VMEM((n, 1), jnp.float32),
        pltpu.VMEM((n, 1), jnp.float32),
        pltpu.VMEM((n, lanes), jnp.float32),
    ]


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, *refs, n_k: int, **kw):
    # lens_ref: SMEM (B,) int32 scalar-prefetch; k/v blocks (1, bk, KV * d)
    j = pl.program_id(1)
    _decode_step(
        lens_ref[pl.program_id(0)], j, j == n_k - 1, q_ref, k_ref[0], v_ref[0],
        *refs, r=1, window=None, **kw,
    )


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
        return (b, jnp.minimum(j, last_live_block(lens[b], bk)), 0)

    def row(b, j, lens):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((1, KV, G, d), row),
            pl.BlockSpec((1, bk, KV * d), kv_block),
            pl.BlockSpec((1, bk, KV * d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, d), row),
        scratch_shapes=_decode_scratch(q, 1),
    )
    kern = functools.partial(
        _decode_kernel, bk=bk, n_k=n_k, scale=scale,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q, k.reshape(B, S, KV * d), v.reshape(B, S, KV * d))


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


def paged_work_list(
    tables: jax.Array,    # (B, n_blk) int32 logical -> physical block
    lengths: jax.Array,   # (B,) int32 live tokens per row
    bs: int,              # tokens per block
) -> tuple[jax.Array, jax.Array]:
    """The paged kernel's work items: ``(work, n_work)``.  Row ``b`` gets
    ``n_b = ceil(clamp(len_b, 1, n_blk * bs) / bs)`` items, one per logical
    block ``j < n_b`` it reads, encoded ``b * n_blk + j`` (the flat index
    of its table entry), rows in order: the blocks the XLA twin walks, so
    the two read the same keys.  Every row keeps at least one item, so the
    kernel writes every output row.
    ``n_work = sum(n_b)`` is traced; the array keeps its static size
    ``B * n_blk``, its tail padded with the last item, so an index map read
    one step ahead stays in range."""
    B, n_blk = tables.shape
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, n_blk * bs)
    n = (lengths + bs - 1) // bs
    ends = jnp.cumsum(n)
    n_work = ends[-1]
    i = jnp.minimum(jnp.arange(B * n_blk, dtype=jnp.int32), n_work - 1)
    # item i's row: the rows that end at or before it, one vector compare
    b = jnp.sum(ends[None, :] <= i[:, None], axis=1, dtype=jnp.int32)
    j = i - (ends[b] - n[b])
    return b * n_blk + j, n_work


def _paged_decode_kernel(work_ref, n_work_ref, lens_ref, table_ref, layer_ref,
                         q_ref, k_ref, v_ref, *refs, n_blk: int, **kw):
    # scalar prefetch (SMEM): work (W,), n_work (1,), lens (B,), table
    # (B * n_blk,), layer (1,) int32; the table and layer are only read by
    # the index maps.  Grid step i runs work item work[i] = b * n_blk + j.
    # k/v blocks are one packed pool block (1, bs // r, r * KV * d): token
    # t of the block, head h sits at row t // r, lanes (t % r) * KV * d + h * d.
    i = pl.program_id(0)
    item = work_ref[i]
    b = item // n_blk
    # a row's items are consecutive: it ends where the next item's row
    # differs, or at the list's last item (the padded tail repeats it)
    nxt = work_ref[jnp.minimum(i + 1, work_ref.shape[0] - 1)]
    last = (i == n_work_ref[0] - 1) | (nxt // n_blk != b)
    _decode_step(
        lens_ref[b], item % n_blk, last, q_ref, k_ref[0], v_ref[0], *refs, **kw
    )


def flash_decode_paged_pallas(
    q: jax.Array,         # (B, KV, G, d) one query token per (row, head)
    kpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d) packed pools
    vpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d)
    tables: jax.Array,    # (B, n_blk) int32 logical -> physical block
    lengths: jax.Array,   # (B,) int32 live tokens per row (traced)
    layer: jax.Array,     # () int32 which layer's pool to read (traced)
    *,
    work: tuple[jax.Array, jax.Array] | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``work`` is :func:`paged_work_list` of these tables and lengths,
    which a caller running every layer builds once; without it the kernel
    builds it."""
    B, KV, G, d = q.shape
    rows, lanes = kpool.shape[2:]
    r = lanes // (KV * d)
    bs = rows * r
    n_blk = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    if work is None:
        work = paged_work_list(tables, lengths, bs)
    items, n_work = work
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, n_blk * bs)
    tables = tables.astype(jnp.int32).reshape(-1)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    def kv_block(i, items, n_work, lens, tabs, li):
        return (li[0], tabs[items[i]], 0, 0)

    def row(i, items, n_work, lens, tabs, li):
        return (items[i] // n_blk, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec((1, KV, G, d), row),
            pl.BlockSpec((None, 1, rows, lanes), kv_block),
            pl.BlockSpec((None, 1, rows, lanes), kv_block),
        ],
        out_specs=pl.BlockSpec((1, KV, G, d), row),
        scratch_shapes=_decode_scratch(q, r),
    )
    kern = functools.partial(
        _paged_decode_kernel,
        r=r, n_blk=n_blk, bk=bs, scale=scale, window=window,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
        name="decode_attention_paged",
    )(items, jnp.reshape(n_work, (1,)).astype(jnp.int32), lengths, tables, layer,
      q, kpool, vpool)


def decode_attention_paged_xla(
    q: jax.Array,         # (B, KV, G, d)
    kpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d)
    vpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d)
    tables: jax.Array,    # (B, n_blk) int32
    lengths: jax.Array,   # (B,) int32
    layer: jax.Array,     # () int32
    *,
    window: int | None = None,
) -> jax.Array:
    """Gather-based jnp twin of the paged kernel: the same blocked
    recurrence as :func:`decode_attention_xla` with the KV block fetched
    through the block table (one ``(B,)`` gather of layer ``layer`` per live
    split, unpacked back to ``(B, bs, KV, d)``) instead of a dynamic slice.
    At ``bk == block_size`` the two twins are bitwise equal on equal logical
    contents — the paged serve engine's differential oracle rests on
    this."""
    B, KV, G, d = q.shape
    rows, lanes = kpool.shape[2:]
    bs = rows * (lanes // (KV * d))
    scale = 1.0 / math.sqrt(d)
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, tables.shape[1] * bs)
    tables = tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    n_live = jnp.max((lengths + bs - 1) // bs)

    def body(state):
        j, m, l, acc = state
        phys = jax.lax.dynamic_slice_in_dim(tables, j, 1, axis=1)[:, 0]
        kb = kpool[layer, phys].reshape(B, bs, KV, d)
        vb = vpool[layer, phys].reshape(B, bs, KV, d)
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
