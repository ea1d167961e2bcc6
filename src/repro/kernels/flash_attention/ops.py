"""jit'd GQA-aware wrappers over the flash attention kernels.

Two entry points:

  * :func:`flash_attention` — prefill/training attention.  KV heads are
    indexed *inside* the kernel (q rows `B*KV*G`, k/v rows `B*KV`), never
    broadcast to the G query groups in HBM.  ``q_offset``/``kv_len`` ride
    as a traced scalar-prefetch operand whenever they are non-trivial, so
    distinct cached lengths share one compilation; the plain
    (offset 0, full keys) prefill keeps the fully static fast path.
  * :func:`decode_attention` — the serve engine's ragged flash-decoding
    path: one query token per slot, per-row live lengths traced, cache-
    native ``(B, S, KV, d)`` k/v layout (zero copies on the donated decode
    loop).  KV-axis tile sizes come from the paper's blocking search
    (``core.mapper.choose_matmul_tiles`` on the score matmul).  On CPU the
    default substrate is the kernel's jnp twin
    (``decode_attention_xla``, while-loop over live splits); pass
    ``impl="pallas"`` (+ ``interpret=True`` off-TPU) to run the kernel body
    itself, as the differential tests do.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.decode_attention import (
    decode_attention_paged_xla,
    decode_attention_xla,
    flash_decode_paged_pallas,
    flash_decode_pallas,
)
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _split_heads(q, k, v):
    """(B,Tq,KV,G,d)/(B,Tk,KV,d) -> row-major (B*KV*G,Tq,d)/(B*KV,Tk,d).
    No GQA broadcast: the kernel's k/v index maps divide q rows by G."""
    B, Tq, KV, G, d = q.shape
    Tk = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * KV * G, Tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Tk, d)
    return qf, kf, vf


def _pad_blocks(qf, kf, vf, Tq, Tk, bq, bk):
    pq, pk = (-Tq) % bq, (-Tk) % bk
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    return qf, kf, vf, pk


@partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret")
)
def _fa_static(q, k, v, *, causal, window, bq, bk, interpret):
    B, Tq, KV, G, d = q.shape
    Tk = k.shape[1]
    bq_, bk_ = min(bq, Tq), min(bk, Tk)
    qf, kf, vf = _split_heads(q, k, v)
    qf, kf, vf, pk = _pad_blocks(qf, kf, vf, Tq, Tk, bq_, bk_)
    out = flash_attention_pallas(
        qf, kf, vf, bq=bq_, bk=bk_, causal=causal, window=window,
        q_offset=0, kv_len=Tk if pk else None, g=G, interpret=interpret,
    )
    out = out[:, :Tq].reshape(B, KV, G, Tq, d).transpose(0, 3, 1, 2, 4)
    return out


@partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret")
)
def _fa_dynamic(q, k, v, q_offset, kv_len, *, causal, window, bq, bk,
                interpret):
    B, Tq, KV, G, d = q.shape
    Tk = k.shape[1]
    bq_, bk_ = min(bq, Tq), min(bk, Tk)
    qf, kf, vf = _split_heads(q, k, v)
    qf, kf, vf, _ = _pad_blocks(qf, kf, vf, Tq, Tk, bq_, bk_)
    out = flash_attention_pallas(
        qf, kf, vf, bq=bq_, bk=bk_, causal=causal, window=window,
        q_offset=q_offset, kv_len=jnp.minimum(kv_len, Tk), g=G,
        interpret=interpret,
    )
    out = out[:, :Tq].reshape(B, KV, G, Tq, d).transpose(0, 3, 1, 2, 4)
    return out


def flash_attention(
    q: jax.Array,       # (B, Tq, KV, G, d) grouped-query layout
    k: jax.Array,       # (B, Tk, KV, d)
    v: jax.Array,       # (B, Tk, KV, d)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int | jax.Array = 0,
    kv_len: int | jax.Array | None = None,
    bq: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, Tq, KV, G, d).  Non-trivial ``q_offset``/``kv_len``
    (Python ints included) are traced, so every cached length shares one
    compiled program; the trivial prefill case stays fully static."""
    interp = _should_interpret() if interpret is None else interpret
    static = (
        not isinstance(q_offset, jax.Array)
        and int(q_offset) == 0
        and not isinstance(kv_len, jax.Array)
        and kv_len is None
    )
    if static:
        return _fa_static(
            q, k, v, causal=causal, window=window, bq=bq, bk=bk,
            interpret=interp,
        )
    Tk = k.shape[1]
    return _fa_dynamic(
        q, k, v,
        jnp.asarray(q_offset, jnp.int32),
        jnp.asarray(Tk if kv_len is None else kv_len, jnp.int32),
        causal=causal, window=window, bq=bq, bk=bk, interpret=interp,
    )


@functools.lru_cache(maxsize=None)
def _pick_decode_bk(S: int, G: int, d: int, impl: str) -> int:
    """KV-axis block for the decode score matmul (M=G, N=S, K=d), from the
    paper's blocking search, clamped to a divisor of the cache extent so
    the ring buffer is never padded (padding would copy the donated KV).

    The search optimizes VMEM reuse, but the ragged skip granularity is
    ceil(len/bk) — one giant block would always read the whole cache,
    defeating flash-decoding's point — so the tile is capped per substrate:
    512 for the Pallas kernel (DMA efficiency still wants wide blocks) and
    64 for the jnp twin, where a while-loop iteration is cheap and typical
    live lengths are far below the cache extent (measured on the serve
    shapes: bk=64 halves the op time vs the dense oracle where bk=512
    loses to it)."""
    from repro.core.mapper import choose_matmul_tiles

    t = choose_matmul_tiles(max(G, 8), S, d)
    cap = 512 if impl == "pallas" else 64
    b = max(8, min(t.bn, cap, S))
    while S % b:
        b -= 1
    return b


def decode_attention(
    q: jax.Array,         # (B, KV, G, d) one query token per slot
    k: jax.Array,         # (B, S, KV, d) cache-native layout
    v: jax.Array,         # (B, S, KV, d)
    lengths: jax.Array,   # (B,) int32 live KV slots per row (traced)
    *,
    bk: int | None = None,
    impl: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Ragged flash-decoding; returns (B, KV, G, d).

    ``impl``: "pallas" (the kernel; interpret-mode off TPU), "xla" (its jnp
    twin — the CPU serving default), or None for backend auto-dispatch.

    ``lengths`` are clamped to ``[1, S]``: a decode step always writes the
    current token before attending (the serve ring invariant), so a live
    row has at least one key, and — unlike ``decode_attention_ref`` —
    length 0 is treated as 1, not as a fully-masked row.
    """
    B, KV, G, d = q.shape
    S = k.shape[1]
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    bk_ = _pick_decode_bk(S, G, d, impl) if bk is None else max(1, min(bk, S))
    while S % bk_:
        bk_ -= 1
    if impl == "xla":
        return decode_attention_xla(q, k, v, lengths, bk=bk_)
    interp = _should_interpret() if interpret is None else interpret
    return flash_decode_pallas(q, k, v, lengths, bk=bk_, interpret=interp)


def decode_attention_paged(
    q: jax.Array,         # (B, KV, G, d) one query token per row
    kpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d) packed pools
    vpool: jax.Array,     # (L, num_blocks, bs // r, r * KV * d)
    tables: jax.Array,    # (B, n_blk) int32 logical -> physical block
    lengths: jax.Array,   # (B,) int32 live tokens per row (traced)
    layer: jax.Array,     # () int32 which layer's pool to read (traced)
    *,
    work: tuple[jax.Array, jax.Array] | None = None,
    window: int | None = None,
    impl: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-table-indirect ragged flash-decoding over layer ``layer`` of
    the stacked, packed pools (``serve.kvcache.pack_blocks``); returns
    (B, KV, G, d).  ``work`` is the kernel's work list
    (``decode_attention.paged_work_list``); the XLA twin has none.

    The KV split size is the pool's block size (splits are physical
    blocks), so there is no ``bk`` knob: the logical reduction order is
    fixed by the paged layout, which is what makes the XLA twin bitwise
    comparable to the contiguous twin at ``bk == block_size``.  ``impl``
    dispatches exactly like :func:`decode_attention`."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return decode_attention_paged_xla(
            q, kpool, vpool, tables, lengths, layer, window=window
        )
    interp = _should_interpret() if interpret is None else interpret
    return flash_decode_paged_pallas(
        q, kpool, vpool, tables, lengths, layer,
        work=work, window=window, interpret=interp,
    )
