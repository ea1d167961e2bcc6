"""Flash attention Pallas kernel (online softmax, TPU tiling).

Grid (BH, Tq/bq, Tk/bk) with the KV dimension innermost; running max /
normalizer / fp32 accumulator live in VMEM scratch across KV steps.  The
causal/sliding-window mask is computed from absolute positions derived from
the grid indices (plus a q_offset for cached decode), so no S x S mask
tensor ever materializes - the kernel is the Pallas twin of
arch/attention.blockwise_attention, which doubles as its oracle.

Two variants share the body math:

  * static: ``q_offset``/``kv_len`` baked as Python ints — the prefill fast
    path (offset 0, full keys; a shape-derived kv_len covers block padding).
  * dynamic: ``q_offset``/``kv_len`` are a traced ``(2,)`` int32
    scalar-prefetch operand, so cached-decode calls at every distinct
    length share ONE compilation; k/v blocks past ``ceil(kv_len/bk)`` are
    aliased to the last live block (eliding the fetch) and skip compute.

GQA is resolved in the kernel: q rows are ``B*KV*G`` while k/v rows are
``B*KV``, and the k/v index maps divide the q row id by ``g`` — the KV
tensors are never broadcast G-fold in HBM.

Per DESIGN.md: TPU adaptation keeps the MXU busy with (bq x d) @ (d x bk)
score tiles and (bq x bk) @ (bk x d) value tiles; bq/bk default to the
hardware-aligned blocks the core blocking search picks for the score matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def reset_carry(m_ref, l_ref, acc_ref):
    """Reset the online-softmax running state at the first KV step."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def finalize_out(o_ref, l_ref, acc_ref):
    """Normalize the accumulator into the output block at the last step."""
    o_ref[0, ...] = (
        acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
    ).astype(o_ref.dtype)


def last_live_block(length, bk: int):
    """Index of the last KV block holding live keys; index maps alias dead
    grid steps to it, so the block index never changes past the live
    region and the pipeline elides those fetches."""
    return jnp.maximum((length + bk - 1) // bk - 1, 0)


def _update(
    q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
    *, i: int, j: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None, q_offset, kv_len,
):
    """One (q-block, kv-block) online-softmax step; offset/len may be
    Python ints (static kernel) or traced scalars (dynamic kernel)."""
    q = q_ref[0]                      # (bq, d)
    k = k_ref[0]                      # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                         # (bq, bk)

    q_pos = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    if kv_len is not None:
        ok &= k_pos < kv_len
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, n_k: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None, q_offset: int, kv_len: int | None,
):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        reset_carry(m_ref, l_ref, acc_ref)

    _update(
        q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
        i=i, j=j, bq=bq, bk=bk, scale=scale,
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
    )

    @pl.when(j == n_k - 1)
    def _store():
        finalize_out(o_ref, l_ref, acc_ref)


def _flash_kernel_dyn(
    info_ref,                     # SMEM (2,) int32: [q_offset, kv_len]
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, n_k: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None,
):
    j = pl.program_id(2)
    i = pl.program_id(1)
    kv_len = info_ref[1]

    @pl.when(j == 0)
    def _init():
        reset_carry(m_ref, l_ref, acc_ref)

    @pl.when(j * bk < kv_len)
    def _live():
        _update(
            q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
            i=i, j=j, bq=bq, bk=bk, scale=scale,
            causal=causal, window=window,
            q_offset=info_ref[0], kv_len=kv_len,
        )

    @pl.when(j == n_k - 1)
    def _store():
        finalize_out(o_ref, l_ref, acc_ref)


def flash_attention_pallas(
    q: jax.Array,       # (BH, Tq, d) with BH = BKV * g
    k: jax.Array,       # (BKV, Tk, d)
    v: jax.Array,       # (BKV, Tk, d)
    *,
    bq: int = 256,
    bk: int = 512,
    causal: bool = True,
    window: int | None = None,
    q_offset: int | jax.Array = 0,
    kv_len: int | jax.Array | None = None,
    g: int = 1,
    interpret: bool = False,
) -> jax.Array:
    BH, Tq, d = q.shape
    BKV, Tk, _ = k.shape
    assert BH == BKV * g, (BH, BKV, g)
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, ((Tq, Tk), (bq, bk))
    n_k = Tk // bk
    scale = 1.0 / math.sqrt(d)
    dynamic = isinstance(q_offset, jax.Array) or isinstance(kv_len, jax.Array)

    scratch = [
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    if not dynamic:
        kern = functools.partial(
            _flash_kernel, n_k=n_k, bq=bq, bk=bk, scale=scale,
            causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
        )
        return pl.pallas_call(
            kern,
            grid=(BH, Tq // bq, n_k),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
                pl.BlockSpec((1, bk, d), lambda b, i, j: (b // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, Tq, d), q.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
        )(q, k, v)

    info = jnp.stack([
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(Tk if kv_len is None else kv_len, jnp.int32),
    ])

    def kv_block(b, i, j, info):
        return (b // g, jnp.minimum(j, last_live_block(info[1], bk)), 0)

    kern = functools.partial(
        _flash_kernel_dyn, n_k=n_k, bq=bq, bk=bk, scale=scale,
        causal=causal, window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, Tq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, info: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_block),
            pl.BlockSpec((1, bk, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j, info: (b, i, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Tq, d), q.dtype),
        interpret=interpret,
    )(info, q, k, v)
