"""Pure-jnp oracle for flash attention (dense softmax, fp32)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(
    q: jax.Array,       # (BH, Tq, d)
    k: jax.Array,       # (BH, Tk, d)
    v: jax.Array,       # (BH, Tk, d)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    kv_len: int | None = None,
) -> jax.Array:
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) / math.sqrt(d)
    q_pos = q_offset + jnp.arange(q.shape[1])[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    ok = jnp.ones(s.shape[1:], bool)
    if causal:
        ok &= q_pos >= k_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    if kv_len is not None:
        ok &= k_pos < kv_len
    s = jnp.where(ok[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,        # (B, KV, G, d)
    k: jax.Array,        # (B, S, KV, d)
    v: jax.Array,        # (B, S, KV, d)
    lengths: jax.Array,  # (B,) int32
) -> jax.Array:
    """Dense-softmax oracle for ragged flash-decoding: row b attends over
    exactly cache slots [0, lengths[b])."""
    d = q.shape[-1]
    s = jnp.einsum("bhgd,bshd->bhgs", q, k).astype(jnp.float32) / math.sqrt(d)
    live = jnp.arange(k.shape[1])[None, :] < lengths[:, None]   # (B, S)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v.dtype), v
    ).astype(q.dtype)


def decode_attention_paged_ref(
    q: jax.Array,        # (B, KV, G, d)
    kpool: jax.Array,    # (L, num_blocks, bs // r, r * KV * d) packed pools
    vpool: jax.Array,    # (L, num_blocks, bs // r, r * KV * d)
    tables: jax.Array,   # (B, n_blk) int32
    lengths: jax.Array,  # (B,) int32
    layer: int | jax.Array,
    *,
    window: int | None = None,
) -> jax.Array:
    """Dense oracle for paged decode: materialize each row's logical KV
    sequence by gathering its block-table chain out of layer ``layer`` of
    the packed pools and unpacking it (a packed row holds ``r`` consecutive
    tokens, head-major within a token), then run the dense masked softmax
    (optionally sliding-window; logical index == absolute position in the
    paged layout)."""
    B, KV, _, d = q.shape
    # (B, n_blk, bs // r, r * KV * d) -> (B, S, KV, d) dense per-row sequences
    k = jnp.take(kpool[layer], tables, axis=0).reshape(B, -1, KV, d)
    v = jnp.take(vpool[layer], tables, axis=0).reshape(B, -1, KV, d)
    s = jnp.einsum("bhgd,bshd->bhgs", q, k).astype(jnp.float32) / math.sqrt(d)
    k_idx = jnp.arange(k.shape[1])[None, :]
    live = k_idx < lengths[:, None]                             # (B, S)
    if window is not None:
        live &= k_idx > (lengths[:, None] - 1 - window)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v.dtype), v
    ).astype(q.dtype)
