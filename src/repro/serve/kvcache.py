"""Slot-based batched KV cache for the continuous-batching serve engine.

The decode-path cache construction that used to live inline in
``arch/transformer.py`` is carved out here as :func:`build_caches`, plus the
slot-pool primitives the engine needs:

  * ``build_caches(cfg, batch, max_len)`` — the full decoder-side cache
    pytree for every model family (uniform attention stacks, gemma-style
    local:global ring groups, rwkv/rglru recurrent states, hybrid groups).
    Every leaf carries the batch ("slot") axis, including per-row ``pos`` /
    ``len`` counters, so different rows can sit at different sequence
    positions.
  * ``slot_store(big, small, slot)`` — scatter a freshly prefilled
    batch-1 cache into slot ``slot`` of the persistent batched cache.  One
    compiled program handles admission for every slot index.
  * ``mask_prompt_tail(caches, true_len)`` — invalidate the garbage entries
    a right-padded (bucketed) prefill wrote past the real prompt length.
  * ``supports_padded_prefill(cfg)`` — whether bucketed prefill is exact
    for this config (global attention only: ring buffers and recurrent /
    capacity-routed states are polluted by pad tokens).

Slot semantics: admission fully overwrites a slot (the prefilled batch-1
cache starts from zeros, so stale K/V, ``pos`` sentinels and recurrent
states are all replaced); eviction is free — a dead slot keeps decoding
garbage that nothing reads, and the next admission overwrites it.

The PAGED layout (``build_paged_caches`` + :class:`BlockPool` + the
``paged_*`` device ops, below) replaces the contiguous per-slot rings with
a refcounted block pool, per-row block tables and a radix prefix index.
The engine reaches either layout only through :class:`KVLayout`
(:class:`ContiguousKV`, :class:`PagedKV`; see "layouts" at the end).

Donation contract: the engine donates this whole pytree through its jitted
decode/admission programs, so every per-step mutation must be expressible
as an in-place alias of the donated buffers — which is why the primitives
here are ``dynamic_update_slice`` scatters (``slot_store``) and the decode
ring write is a per-row ``.at[idx].set`` (layers.multihead_attention): XLA
aliases donated inputs to outputs and the KV tensors are never copied.
The ragged flash-decoding path additionally relies on the ring invariant
these writes maintain — live entries of every cache occupy exactly slots
``[0, min(len, size))`` — to reduce decode masking to one per-row length.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.arch import layers as L
from repro.arch import rglru as G
from repro.arch import rwkv as R
from repro.configs.base import ModelConfig


def _stack(n: int, f) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *([f()] * n))


def build_caches(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    """Decode caches for ``batch`` slots of ``max_len`` positions each."""
    if cfg.family == "hybrid":
        ng, rem = divmod(cfg.n_layers, cfg.rnn_per_attention + 1)
        groups = None
        if ng:
            groups = {
                "rnn": _stack(
                    ng,
                    lambda: _stack(
                        cfg.rnn_per_attention,
                        lambda: G.rglru_init_cache(cfg, batch),
                    ),
                ),
                "attn": _stack(
                    ng,
                    lambda: L.init_kv_cache(cfg, batch, max_len, cfg.sliding_window),
                ),
            }
        return {
            "groups": groups,
            "tail": _stack(rem, lambda: G.rglru_init_cache(cfg, batch))
            if rem
            else None,
        }
    if cfg.mixer == "rwkv6":
        return _stack(cfg.n_layers, lambda: R.rwkv_init_cache(cfg, batch))
    if cfg.global_every:
        ge = cfg.global_every
        ng = cfg.n_layers // ge
        n_tail = cfg.n_layers - ng * ge

        def local():
            return L.init_kv_cache(cfg, batch, max_len, cfg.sliding_window)

        return {
            "groups": {
                "local": _stack(ng, lambda: _stack(ge - 1, local)),
                "global": _stack(ng, lambda: L.init_kv_cache(cfg, batch, max_len)),
            },
            "tail": _stack(n_tail, local) if n_tail else None,
        }
    from repro.arch.transformer import layer_windows

    wins = layer_windows(cfg)
    per = [
        L.init_kv_cache(cfg, batch, max_len, None if int(w) >= 2**30 else int(w))
        for w in wins
    ]
    # stack layerwise: same cache sizes stack cleanly when homogeneous;
    # gemma-style mixed sizes are padded to the largest (ring semantics
    # keep the window correct).
    size = max(p["k"].shape[1] for p in per)

    def padded(p):
        s = p["k"].shape[1]
        if s == size:
            return p
        padk = jnp.zeros((batch, size - s) + p["k"].shape[2:], p["k"].dtype)
        return {
            "k": jnp.concatenate([p["k"], padk], 1),
            "v": jnp.concatenate([p["v"], padk], 1),
            "pos": jnp.concatenate(
                [p["pos"], jnp.full((batch, size - s), 10**9, jnp.int32)], 1
            ),
            "len": p["len"],
        }

    per = [padded(p) for p in per]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def slot_axes(cfg: ModelConfig, max_len: int) -> Any:
    """Per-leaf index of the slot (batch) axis, located by building the
    cache pytree at two batch sizes and diffing shapes — robust to however
    many layer/group axes a family stacks in front (hybrid rnn leaves are
    ``(ng, rnn_per, B, ...)``, attention leaves ``(L, B, size, ...)``, …)."""
    s1 = jax.eval_shape(lambda: build_caches(cfg, 1, max_len))
    s2 = jax.eval_shape(lambda: build_caches(cfg, 2, max_len))

    def diff(a, b):
        return next(i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y)

    return jax.tree.map(diff, s1, s2)


def slot_store(big: Any, small: Any, slot: jax.Array, axes: Any) -> Any:
    """Write row 0 of a batch-1 cache pytree into slot ``slot`` of a
    batched one.  ``slot`` may be traced, so one jit of this function
    serves every admission; ``axes`` is the static tree from
    :func:`slot_axes`."""

    def put(b, s, ax):
        starts = [jnp.int32(0)] * b.ndim
        starts[ax] = jnp.asarray(slot, jnp.int32)
        return jax.lax.dynamic_update_slice(b, s.astype(b.dtype), starts)

    return jax.tree.map(put, big, small, axes)


def take_slot(caches: Any, row: int, axes: Any) -> Any:
    """Slice one slot out of a batched cache pytree, keeping the slot axis
    at extent 1 (the shape :func:`slot_store` expects back)."""
    return jax.tree.map(
        lambda leaf, ax: jax.lax.dynamic_slice_in_dim(leaf, row, 1, axis=ax),
        caches,
        axes,
    )


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def mask_prompt_tail(caches: Any, true_len: jax.Array) -> Any:
    """Invalidate cache entries a right-padded prefill wrote past the real
    prompt: ``pos`` returns to the +1e9 "empty" sentinel (the causal test
    masks those keys) and ``len`` rewinds to the true length.  Only valid
    for non-ring caches, where slot index == position.  ``true_len`` may be
    scalar or per-row ``(B,)`` (rows of a batched admission have different
    prompt lengths)."""
    tl = jnp.asarray(true_len, jnp.int32)

    def fix(path, leaf):
        name = _leaf_name(path)
        if name == "pos":
            idx = jnp.arange(leaf.shape[-1], dtype=jnp.int32)
            return jnp.where(idx >= tl[..., None], jnp.int32(10**9), leaf)
        if name == "len":
            return jnp.broadcast_to(tl, leaf.shape).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, caches)


# ------------------------------------------------------------ paged layout --
#
# The contiguous layout above reserves ``slots x max_len`` KV positions per
# layer — HBM footprint is set by the worst-case sequence (the paper's §6.3
# over-provisioning, at serve granularity).  The paged layout carves the
# same HBM into fixed-size blocks:
#
#   kpool/vpool: (layers, num_blocks, block_size // r, r * kv_heads * head_dim)
#   table:       (batch, max_len // block_size) int32, logical -> physical
#   len:         (batch,) int32 live tokens per row
#
# A pool row packs ``r`` consecutive tokens of a block (``tokens_per_row``):
# token t, head h, dim e lives at row t // r, lane (t % r) * kv_heads *
# head_dim + h * head_dim + e — the row-major memory of (block_size,
# kv_heads, head_dim), so ``pack_blocks``/``unpack_blocks`` are pure
# reshapes.  ``r`` makes a row whole 128-lane tiles, so the TPU stores the
# pool unpadded in the layout the decode kernel reads (a (5, 64) minor pair
# would pad 3.2x under the (8, 128) tiling).
#
# Physical block 0 is the SINK: never allocated, evicted rows point every
# table entry at it so the always-full-batch decode program's garbage
# writes land somewhere harmless.  Host-side ownership (refcounts, the
# free list, the prefix index) lives in :class:`BlockPool`; the device
# pytree is mutated only through the donated pure ops below
# (`paged_store_row_blocks` / `paged_set_row` / `paged_copy_block`, and
# `paged_write_token` inside the decode program), so the decode loop keeps
# the zero-copy donation contract.
#
# Prefix sharing: the pool keys each block by ``(previous physical block,
# tokens written in it)`` — a radix chain, vLLM-style.  Requests whose
# prompts share a leading run of full blocks alias those physical blocks
# (refcount += 1 each).  A partially-filled prompt tail block is shared
# only on an exact content match, and the *attaching* request copies it on
# its first divergent write (copy-on-write); the creating request never
# needs to — appends past the registered fill are masked for every sharer
# (they read only ``[0, their_len)``), so registered content is immutable
# by construction.


def supports_paged(cfg: ModelConfig) -> bool:
    """The paged layout stores one uniform KV pool per layer and masks
    purely by live length, so it requires every layer to be (global)
    attention: ring buffers (sliding windows), recurrent states and hybrid
    stacks have no block-table equivalent here.  MoE FFNs are fine — paging
    only touches the attention KV."""
    return (
        cfg.family in ("dense", "moe")
        and cfg.mixer == "attention"
        and cfg.sliding_window is None
        and not cfg.global_every
    )


def tokens_per_row(kv_heads: int, head_dim: int, block_size: int) -> int:
    """Tokens a packed pool row holds: the fewest whose K (or V) fill whole
    128-lane tiles, ``128 // gcd(kv_heads * head_dim, 128)``, cut to a
    divisor of ``block_size``.  (5, 64) packs 2 tokens into 640 lanes;
    (8, 128) needs no packing."""
    r = 128 // math.gcd(kv_heads * head_dim, 128)
    return math.gcd(r, block_size)


def pack_blocks(blocks: Any, r: int) -> Any:
    """``(..., block_size, kv_heads, head_dim)`` -> packed pool rows
    ``(..., block_size // r, r * kv_heads * head_dim)`` (a reshape)."""
    *lead, bs, kv, hd = blocks.shape
    return blocks.reshape(*lead, bs // r, r * kv * hd)


def unpack_blocks(rows: Any, kv_heads: int, head_dim: int) -> Any:
    """Inverse of :func:`pack_blocks`: packed rows ``(..., n, r * kv_heads *
    head_dim)`` -> tokens ``(..., n * r, kv_heads, head_dim)``."""
    *lead, n, lanes = rows.shape
    r = lanes // (kv_heads * head_dim)
    return rows.reshape(*lead, n * r, kv_heads, head_dim)


def token_slot(
    pos: int, kv_heads: int, head_dim: int, block_size: int
) -> tuple[int, slice]:
    """Row and lanes of a packed pool block that hold token ``pos`` of the
    block (every head): ``pool[layer, block, row, lanes]`` is its K or V,
    shaped ``(kv_heads * head_dim,)``."""
    r = tokens_per_row(kv_heads, head_dim, block_size)
    w = kv_heads * head_dim
    return pos // r, slice((pos % r) * w, (pos % r + 1) * w)


def build_paged_caches(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    num_blocks: int,
    block_size: int,
) -> Any:
    """Paged decode caches: per-layer packed block pools stacked over
    layers, plus per-row block tables / lengths (replicated per layer, as
    every cache leaf has a layer axis; the int32 metadata is negligible)."""
    if not supports_paged(cfg):
        raise ValueError(f"paged KV layout unsupported for {cfg.name}")
    if max_len % block_size:
        raise ValueError(
            f"max_len {max_len} not a multiple of block_size {block_size}"
        )
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    sd = jnp.dtype(cfg.dtype)
    n_blk = max_len // block_size
    L = cfg.n_layers
    r = tokens_per_row(kv, hd, block_size)
    pool = (L, num_blocks, block_size // r, r * kv * hd)

    return {
        "kpool": jnp.zeros(pool, sd),
        "vpool": jnp.zeros(pool, sd),
        "table": jnp.zeros((L, batch, n_blk), jnp.int32),
        "len": jnp.zeros((L, batch), jnp.int32),
    }


def paged_store_row_blocks(
    caches: Any,
    scratch: Any,
    row: jax.Array,
    start_lb: jax.Array,
    phys: jax.Array,
) -> Any:
    """Pack ``len(phys)`` consecutive logical blocks of a freshly prefilled
    contiguous scratch cache (leaves ``(L, n, S, kv, hd)``) into the pool
    blocks ``phys``, starting at logical block ``start_lb`` of scratch row
    ``row``.  All indices are traced — one compilation serves every
    admission per distinct block count.  ``caches`` is donated by the
    engine's jit of this function."""
    n_pack = phys.shape[0]
    L = scratch["k"].shape[0]
    kv, hd = scratch["k"].shape[3:]
    r = caches["kpool"].shape[3] // (kv * hd)
    bs = caches["kpool"].shape[2] * r
    row = jnp.asarray(row, jnp.int32)
    start = jnp.asarray(start_lb, jnp.int32) * bs

    def pack(pool, src):
        blk = jax.lax.dynamic_slice(
            src,
            (jnp.int32(0), row, start, jnp.int32(0), jnp.int32(0)),
            (L, 1, n_pack * bs, kv, hd),
        )
        blocks = pack_blocks(blk[:, 0].reshape(L, n_pack, bs, kv, hd), r)
        # (L, n_pack, bs // r, r * kv * hd) scattered at pool[:, phys]
        return pool.at[:, phys.astype(jnp.int32)].set(blocks.astype(pool.dtype))

    return {
        "kpool": pack(caches["kpool"], scratch["k"]),
        "vpool": pack(caches["vpool"], scratch["v"]),
        "table": caches["table"],
        "len": caches["len"],
    }


def paged_write_token(
    pool: jax.Array,
    layer: jax.Array,
    table: jax.Array,
    pos: jax.Array,
    new: jax.Array,
) -> jax.Array:
    """Write one decode token's K (or V) of every row into layer ``layer``
    of a packed pool: row ``b``'s token ``new[b]`` (kv_heads, head_dim) goes
    to position ``pos[b]``, in block ``table[b, pos[b] // block_size]``.
    The packed rows holding the tokens are gathered, each token's lanes
    replaced, and the rows scattered back: one row scatter, in place in
    the donated (or loop-carried) pool.  The engine guarantees exclusive
    write ownership of a live row's block (fresh allocation or
    copy-on-write before the step), so live rows never collide; evicted
    rows aim every table entry at the sink block, whose garbage nothing
    live reads."""
    B, kv, hd = new.shape
    rows, lanes = pool.shape[2:]
    r = lanes // (kv * hd)
    bs = rows * r
    phys = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    old = pool[layer, phys, off // r].reshape(B, r, kv * hd)
    mine = (jnp.arange(r) == (off % r)[:, None])[:, :, None]     # (B, r, 1)
    tok = new.reshape(B, 1, kv * hd).astype(pool.dtype)
    return pool.at[layer, phys, off // r].set(
        jnp.where(mine, tok, old).reshape(B, lanes)
    )


def paged_set_row(
    caches: Any, row: jax.Array, table_row: jax.Array, length: jax.Array
) -> Any:
    """Write one row's full block table + live length (admission fills it,
    eviction resets it to all-sink / zero).  ``row`` is traced — one
    compilation serves every slot."""
    row = jnp.asarray(row, jnp.int32)
    L = caches["table"].shape[0]
    tab = jnp.broadcast_to(
        table_row.astype(jnp.int32)[None, None, :],
        (L, 1, caches["table"].shape[2]),
    )
    table = jax.lax.dynamic_update_slice(
        caches["table"], tab, (jnp.int32(0), row, jnp.int32(0))
    )
    ln = jnp.broadcast_to(jnp.asarray(length, jnp.int32)[None, None], (L, 1))
    length_ = jax.lax.dynamic_update_slice(caches["len"], ln, (jnp.int32(0), row))
    return {
        "kpool": caches["kpool"],
        "vpool": caches["vpool"],
        "table": table,
        "len": length_,
    }


def paged_copy_block(
    caches: Any, row: jax.Array, lb: jax.Array, src: jax.Array, dst: jax.Array
) -> Any:
    """Copy-on-write: duplicate physical block ``src`` into ``dst`` across
    every layer's pools and repoint row ``row``'s logical block ``lb`` at
    the private copy."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def cow(pool):
        blk = jnp.take(pool, src, axis=1)  # (L, bs // r, r * kv * hd)
        return jax.lax.dynamic_update_slice_in_dim(pool, blk[:, None], dst, axis=1)

    row = jnp.asarray(row, jnp.int32)
    lb = jnp.asarray(lb, jnp.int32)
    table = caches["table"].at[:, row, lb].set(dst)
    return {
        "kpool": cow(caches["kpool"]),
        "vpool": cow(caches["vpool"]),
        "table": table,
        "len": caches["len"],
    }


SINK_BLOCK = 0  # physical block 0: garbage target for dead rows, never owned


class BlockPool:
    """Host-side block ownership for the paged KV cache: a free list,
    per-block refcounts, and the radix-chain prefix index.

    The pool never touches device memory — it decides *which* physical
    blocks a request may read/write, and the engine turns those decisions
    into donated device ops.  Invariants (checked by
    :meth:`assert_invariants` and the fuzz suite):

      * refcount[b] == number of (live request, logical slot) references
        to b, for every non-sink block; sink refcount is never tracked.
      * the free list and the referenced set partition ``[1, num_blocks)``.
      * every prefix-index entry points at a block with refcount >= 1
        (releasing a block to zero drops its index entries), so an idle
        pool is fully free — no leak through the index.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least one allocatable block + sink")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.refcount = [0] * num_blocks
        self.free: list[int] = list(range(num_blocks - 1, 0, -1))  # pop() = 1
        # (prev_physical_block, tokens-in-block) -> physical block.  Full
        # blocks carry block_size tokens; a prompt tail carries fewer, so
        # key tuples of different fills never collide.
        self.index: dict[tuple[int, tuple[int, ...]], int] = {}
        self._keys_of: dict[int, list] = {}
        # blocks held by an EXTERNAL actor (chaos pressure spikes, a future
        # multi-tenant reservation API): invisible to the engine's rows but
        # accounted by assert_invariants so pressure never masquerades as a
        # leak.  Populated only via reserve()/unreserve().
        self.external: set[int] = set()

    @property
    def free_blocks(self) -> int:
        return len(self.free)

    def alloc(self) -> int:
        bid = self.free.pop()
        assert self.refcount[bid] == 0, bid
        self.refcount[bid] = 1
        return bid

    def retain(self, bid: int) -> None:
        assert self.refcount[bid] > 0, f"retain of unowned block {bid}"
        self.refcount[bid] += 1

    def release(self, bid: int) -> None:
        assert self.refcount[bid] > 0, f"release of unowned block {bid}"
        self.refcount[bid] -= 1
        if self.refcount[bid] == 0:
            for key in self._keys_of.pop(bid, ()):
                if self.index.get(key) == bid:
                    del self.index[key]
            self.free.append(bid)

    def reserve(self, n: int) -> list[int]:
        """Withhold up to ``n`` free blocks from the pool on behalf of an
        external actor (the chaos harness's pressure spikes; never the
        engine).  Returns the block ids actually reserved — fewer than
        ``n`` when the pool is drier than asked."""
        got = []
        for _ in range(min(n, len(self.free))):
            bid = self.alloc()
            self.external.add(bid)
            got.append(bid)
        return got

    def unreserve(self, bids: list[int]) -> None:
        """Return externally reserved blocks to the pool."""
        for bid in bids:
            assert bid in self.external, f"unreserve of non-reserved block {bid}"
            self.external.discard(bid)
            self.release(bid)

    def register(self, prev: int, tokens: tuple[int, ...], bid: int) -> None:
        """Expose a block's content to future prefix matches.  First
        registration wins; identical content admitted later simply fails to
        register (it already matched or races a live twin)."""
        key = (prev, tokens)
        if key not in self.index:
            self.index[key] = bid
            self._keys_of.setdefault(bid, []).append(key)

    def match_prefix(self, tokens: list[int]) -> tuple[list[int], int | None]:
        """Walk the radix chain over the prompt: returns (shared full
        blocks, shared-tail block or None).  The tail matches only when
        every full block matched and the partial content is identical."""
        bs = self.block_size
        shared: list[int] = []
        prev = -1
        n_full = len(tokens) // bs
        for i in range(n_full):
            bid = self.index.get((prev, tuple(tokens[i * bs : (i + 1) * bs])))
            if bid is None:
                return shared, None
            shared.append(bid)
            prev = bid
        tail = tokens[n_full * bs :]
        if not tail or len(shared) != n_full:
            return shared, None
        return shared, self.index.get((prev, tuple(tail)))

    def to_state(self) -> dict:
        """JSON-serializable snapshot of the full ownership state, prefix
        index included — the recovery manager embeds this in the engine
        snapshot manifest so a restored pool keeps aliasing the restored
        device blocks."""
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "refcount": list(self.refcount),
            "free": list(self.free),
            "external": sorted(self.external),
            "index": [
                [prev, list(tokens), bid]
                for (prev, tokens), bid in self.index.items()
            ],
        }

    @classmethod
    def from_state(cls, state: dict) -> "BlockPool":
        """Rebuild a pool from :meth:`to_state` output (``_keys_of`` is
        re-derived from the index)."""
        pool = cls(int(state["num_blocks"]), int(state["block_size"]))
        pool.refcount = [int(c) for c in state["refcount"]]
        pool.free = [int(b) for b in state["free"]]
        pool.external = {int(b) for b in state["external"]}
        pool.index = {}
        pool._keys_of = {}
        for prev, tokens, bid in state["index"]:
            pool.register(int(prev), tuple(int(t) for t in tokens), int(bid))
        return pool

    def assert_invariants(self, live_refs: dict[int, int]) -> None:
        """``live_refs``: physical block -> reference count derived from
        the engine's live rows.  Raises on any ownership drift."""
        for bid in range(1, self.num_blocks):
            want = live_refs.get(bid, 0) + (1 if bid in self.external else 0)
            assert self.refcount[bid] == want, (
                f"block {bid}: refcount {self.refcount[bid]} != live refs {want}"
            )
        free_set = set(self.free)
        assert len(free_set) == len(self.free), "free list has duplicates"
        assert SINK_BLOCK not in free_set, "sink block leaked into free list"
        for bid in free_set:
            assert self.refcount[bid] == 0, f"free block {bid} has refs"
        owned = {b for b, c in enumerate(self.refcount) if c > 0}
        assert owned | free_set == set(range(1, self.num_blocks)), (
            "free list + owned blocks do not partition the pool"
        )
        for key, bid in self.index.items():
            assert self.refcount[bid] > 0, (
                f"index entry {key} -> {bid} outlives its block"
            )


def supports_padded_prefill(cfg: ModelConfig) -> bool:
    """Bucketed (right-padded) prefill is exact only when every layer is
    global attention: pad tokens never enter a key window (they are causally
    ahead and later masked by :func:`mask_prompt_tail`).  Ring buffers could
    be overwritten by pad slots, recurrent states integrate pad tokens, and
    MoE capacity routing lets pad tokens change real tokens' drop pattern."""
    return (
        cfg.family == "dense"
        and cfg.mixer == "attention"
        and cfg.sliding_window is None
        and cfg.moe is None
    )


# ----------------------------------------------------------------- layouts --


@dataclasses.dataclass
class PagedRow:
    """Block ownership of one paged request (host side)."""

    blocks: list[int]            # logical block -> physical, len == total
    plen: int                    # prompt tokens
    n_shared_full: int           # leading full blocks aliased via the index
    tail_shared: bool            # partial prompt tail aliased (CoW pending)
    cow_dst: int | None          # pre-allocated CoW target for the tail

    def hit_tokens(self, block_size: int) -> int:
        """Prompt tokens aliased through the prefix index (at reserve,
        before copy-on-write privatises the tail)."""
        tail = self.plen % block_size if self.tail_shared else 0
        return self.n_shared_full * block_size + tail

    def owned(self) -> list[int]:
        """Every block this row holds a reference to."""
        return self.blocks + ([] if self.cow_dst is None else [self.cow_dst])


def pool_sums(caches: Any) -> jax.Array:
    """Per-block ``|K| + |V|`` sums over every layer, row and lane."""
    total = lambda p: jnp.sum(jnp.abs(p.astype(jnp.float32)), axis=(0, 2, 3))
    return total(caches["kpool"]) + total(caches["vpool"])


class KVLayout:
    """The engine's one KV-layout seam, picked by :func:`make_layout`: the
    engine asks it every question whose answer depends on the layout.  The
    engine owns the device pytree (``Engine.caches``); each method that
    writes it takes ``caches`` and returns the new pytree, through programs
    that donate it.  A slot's KV is *reserved* at admission, *published*
    once its prompt is prefilled into a contiguous scratch, and *released*
    at eviction, or *dropped* if never published (a prefill lane cancelled
    or preempted mid-prompt, which wrote nothing to the device).

    ``stats`` is ``Engine.stats``; a layout adds its own counters to it.
    ``rows`` maps each published slot to its ownership record.  The
    defaults are a layout's without a block pool: nothing to reserve, copy
    on write, count or audit."""

    pool: BlockPool | None = None

    def __init__(self, cfg: ModelConfig, scfg: Any, stats: dict):
        self.cfg = cfg
        self.batch = scfg.scheduler.batch
        self.max_len = scfg.max_len
        self.stats = stats
        self.rows: dict[int, PagedRow] = {}

    def check_request(self, rid: int, n_tokens: int) -> None:
        """Raise if a request of ``n_tokens`` KV tokens could never fit."""

    def fits(self, prompt: np.ndarray, budget: int) -> bool:
        """Whether :meth:`reserve` would take the request now."""
        return True

    def reserve(self, slot: int, prompt: np.ndarray, budget: int) -> dict | None:
        """Claim the KV of a request about to take ``slot``: the
        ``serve.lane.start`` span's metadata, or None (nothing claimed)."""
        return {}

    def register(self, slot: int, prompt: np.ndarray) -> None:
        """Offer ``slot``'s reserved prompt to later prefix matches."""

    def publish(
        self, caches: Any, slot: int, scratch: Any, row: int, plen: int
    ) -> tuple[Any, dict]:
        """Install row ``row`` of a prefilled contiguous ``scratch`` (its
        first ``plen`` positions are the prompt) as ``slot``'s KV.  Returns
        the new caches and the ``serve.lane.install`` span's metadata."""
        raise NotImplementedError

    def release(self, caches: Any, slot: int) -> Any:
        """Evict a published slot."""
        return caches

    def drop(self, slot: int) -> None:
        """Return a reservation that was never published."""

    def begin_step(self) -> None:
        """Start a step's record of legally written blocks."""

    def before_decode(self, caches: Any, slots: dict) -> Any:
        """Prepare the live ``slots`` (slot -> state with ``emitted``)."""
        return caches

    def decode_work(self, slots: dict) -> dict:
        """The layout's part of the ``serve.decode`` span's metadata."""
        return {}

    def audit(self, caches: Any) -> list[tuple[int, list[int]]]:
        """Blocks corrupt since the last audit, with their owners' slots."""
        return []

    def live_refs(self) -> dict[int, int]:
        """Block -> references held by reserved and published rows."""
        return {}

    def to_state(self) -> dict:
        """The snapshot's ``rows`` and ``pool`` (JSON-safe)."""
        return {"rows": {}, "pool": None}

    def from_state(self, state: dict, caches: Any) -> None:
        """Restore :meth:`to_state` output beside the restored ``caches``."""


class ContiguousKV(KVLayout):
    """One ``(slots, max_len)`` ring per layer, sized by the worst case.
    Publishing masks the row's right-padded tail and scatters it into the
    slot; eviction writes nothing, since the next admission overwrites the
    whole slot."""

    def __init__(self, cfg: ModelConfig, scfg: Any, stats: dict):
        super().__init__(cfg, scfg, stats)
        axes = slot_axes(cfg, self.max_len)

        def slot_install(big, scratch, row, slot, true_len):
            small = mask_prompt_tail(take_slot(scratch, row, axes), true_len)
            return slot_store(big, small, slot, axes)

        self._install = jax.jit(slot_install, donate_argnums=(0,))

    def init_caches(self) -> Any:
        return build_caches(self.cfg, self.batch, self.max_len)

    def publish(self, caches, slot, scratch, row, plen):
        caches = self._install(
            caches, scratch, jnp.int32(row), jnp.int32(slot), jnp.int32(plen)
        )
        return caches, {}


class PagedKV(KVLayout):
    """The paged pool: a :class:`BlockPool` decides which physical blocks
    each request owns, ``rows`` records them per published slot, and the
    donated ``paged_*`` programs write the device.  With ``kv_checksum``
    (or ABFT, which localizes through it) a host mirror of per-block sums
    is audited against the blocks legally written (``touched``)."""

    def __init__(self, cfg: ModelConfig, scfg: Any, stats: dict):
        if not supports_paged(cfg):
            raise ValueError(
                f"kv_layout='paged' needs all-global attention; "
                f"{cfg.name} has ring/recurrent/hybrid caches"
            )
        super().__init__(cfg, scfg, stats)
        self.block_size = scfg.kv.block_size
        self.prefix_sharing = scfg.kv.prefix_sharing
        self.pool = BlockPool(scfg.resolved_num_blocks(), self.block_size)
        self._reserved: dict[int, PagedRow] = {}
        self._sink_row = np.zeros((self.max_len // self.block_size,), np.int32)
        self.pack_row = jax.jit(paged_store_row_blocks, donate_argnums=(0,))
        self.set_row = jax.jit(paged_set_row, donate_argnums=(0,))
        self.copy_block = jax.jit(paged_copy_block, donate_argnums=(0,))
        armed = scfg.durability.kv_checksum or scfg.kernel.abft != "off"
        self._sums = jax.jit(pool_sums) if armed else None
        self._mirror: np.ndarray | None = None
        self.touched: set[int] = set()

    def init_caches(self) -> Any:
        caches = build_paged_caches(
            self.cfg, self.batch, self.max_len, self.pool.num_blocks,
            self.block_size,
        )
        self._rebaseline(caches)
        return caches

    def _rebaseline(self, caches: Any) -> None:
        if self._sums is not None:
            self._mirror = np.asarray(self._sums(caches))

    def check_request(self, rid, n_tokens):
        # never let one request outgrow the whole pool: its admission would
        # wait forever for blocks that can't exist, and shrinking its budget
        # would quietly diverge from the contiguous oracle.  With the
        # default pool sizing (batch * max_len tokens) this never triggers.
        cap = (self.pool.num_blocks - 1) * self.block_size
        if n_tokens > cap:
            raise ValueError(
                f"request {rid} needs {n_tokens} KV tokens but the whole "
                f"pool holds {cap}; grow num_blocks or shorten the request"
            )

    def _plan(self, prompt, budget):
        """The one copy of admission's block arithmetic: the worst-case
        reservation minus prefix aliases, plus a copy-on-write target when
        the shared tail will be written.  Returns (free blocks taken, total
        blocks, shared full blocks, shared tail or None, cow)."""
        total = -(-(len(prompt) + budget) // self.block_size)
        full, tail = [], None
        if self.prefix_sharing:
            full, tail = self.pool.match_prefix(prompt.tolist())
        cow = tail is not None and budget > 1
        need = total - len(full) - (tail is not None) + cow
        return need, total, full, tail, cow

    def fits(self, prompt, budget):
        return self._plan(prompt, budget)[0] <= self.pool.free_blocks

    def reserve(self, slot, prompt, budget):
        """Retain the prefix aliases, allocate the rest, and set aside the
        copy-on-write target, so the first divergent write can never be
        starved by admissions racing it to the free list."""
        need, total, full, tail, cow = self._plan(prompt, budget)
        if need > self.pool.free_blocks:
            return None
        blocks = full + ([] if tail is None else [tail])
        for b in blocks:
            self.pool.retain(b)
        while len(blocks) < total:
            blocks.append(self.pool.alloc())
        row = PagedRow(
            blocks=blocks,
            plen=len(prompt),
            n_shared_full=len(full),
            tail_shared=tail is not None,
            cow_dst=self.pool.alloc() if cow else None,
        )
        self._reserved[slot] = row
        hit = row.hit_tokens(self.block_size)
        self.stats["prompt_tokens"] += row.plen
        self.stats["prefix_hit_tokens"] += hit
        return {"hit_tokens": hit}

    def register(self, slot, prompt):
        """Publish the reserved row's prompt blocks in the radix prefix
        index.  Monolithic admission registers at reserve, since it
        publishes within the same step; chunked admission at install, since
        a block whose K/V is not packed yet must never be aliased."""
        if not self.prefix_sharing:
            return
        row, bs, toks = self._reserved[slot], self.block_size, prompt.tolist()
        n_full = row.plen // bs
        prev = -1
        for i in range(n_full):
            self.pool.register(prev, tuple(toks[i * bs : (i + 1) * bs]), row.blocks[i])
            prev = row.blocks[i]
        tail = tuple(toks[n_full * bs :])
        if tail and n_full < len(row.blocks):
            self.pool.register(prev, tail, row.blocks[n_full])

    def publish(self, caches, slot, scratch, row, plen):
        """Set the slot's block table and length, then pack the scratch
        row's prompt blocks into the pool, skipping those aliased from the
        prefix index."""
        r = self._reserved.pop(slot)
        bs = self.block_size
        table = np.full(self._sink_row.shape, SINK_BLOCK, np.int32)
        table[: len(r.blocks)] = r.blocks
        caches = self.set_row(
            caches, jnp.int32(slot), jnp.asarray(table), jnp.int32(plen)
        )
        start = r.n_shared_full
        n_pack = -(-plen // bs) - start - (1 if r.tail_shared else 0)
        if n_pack > 0:
            caches = self.pack_row(
                caches,
                {"k": scratch["k"], "v": scratch["v"]},
                jnp.int32(row),
                jnp.int32(start),
                jnp.asarray(r.blocks[start : start + n_pack], jnp.int32),
            )
        self.rows[slot] = r
        if self._sums is not None:
            # aliased prefix blocks are untouched on the device, but marking
            # them is a harmless over-approximation
            self.touched.update(r.blocks)
        return caches, {"packed": max(n_pack, 0)}

    def release(self, caches, slot):
        """Aim the row's device table at the sink (the always-full-batch
        decode keeps writing through dead rows, and these blocks are about
        to be reused) and return every block it owns, a pending CoW
        reservation included."""
        row = self.rows.pop(slot)
        caches = self.set_row(
            caches, jnp.int32(slot), jnp.asarray(self._sink_row), jnp.int32(0)
        )
        for b in row.owned():
            self.pool.release(b)
        return caches

    def drop(self, slot):
        for b in self._reserved.pop(slot).owned():
            self.pool.release(b)

    def begin_step(self):
        self.touched = {SINK_BLOCK}

    def before_decode(self, caches, slots):
        """Give every live row still aliasing a shared prompt tail its
        reserved private copy (its first divergent write lands at ``plen``,
        inside that block), then mark the block each live row's decode
        write lands in: position ``plen + emitted - 1``."""
        bs = self.block_size
        pending = [s for s in sorted(slots) if self.rows[s].cow_dst is not None]
        if pending:
            with jax.profiler.TraceAnnotation("serve.cow", n=len(pending)):
                for s in pending:
                    row = self.rows[s]
                    lb = row.plen // bs
                    src = row.blocks[lb]
                    caches = self.copy_block(
                        caches, jnp.int32(s), jnp.int32(lb), jnp.int32(src),
                        jnp.int32(row.cow_dst),
                    )
                    self.pool.release(src)
                    if self._sums is not None:
                        self.touched.add(row.cow_dst)
                    row.blocks[lb], row.cow_dst = row.cow_dst, None
                    row.tail_shared = False
        if self._sums is not None:
            for s, st in slots.items():
                row = self.rows[s]
                self.touched.add(row.blocks[(row.plen + st.emitted - 1) // bs])
        return caches

    def decode_work(self, slots):
        """The paged decode kernel's work items per layer this step, from
        the host's rows (``decode_attention.paged_work_list`` counts the
        same on the device from the lengths): one per block a live row
        reads through its newest token, and one for each row that holds no
        request, whose length is 0.  ``stats`` sums it beside the whole
        (slots x blocks) grid."""
        bs = self.block_size
        live = sum(-(-(self.rows[s].plen + st.emitted) // bs) for s, st in slots.items())
        work = self.batch - len(slots) + live
        self.stats["decode_work_items"] += work
        self.stats["decode_grid_full"] += self.batch * self.max_len // bs
        return {"work": work}

    def audit(self, caches):
        """Recompute the per-block sums: a block that changed without a
        legal write since the last audit is corrupt.  NaN sums compare
        equal to themselves here, so an already-quarantined poisoned block
        does not re-fire once it sits idle in the free list."""
        if self._sums is None:
            return []
        sums = np.asarray(self._sums(caches))
        prev, self._mirror = self._mirror, sums
        changed = (sums != prev) & ~(np.isnan(sums) & np.isnan(prev))
        if self.touched:
            changed[list(self.touched)] = False
        return [
            (b, [s for s, row in self.rows.items() if b in row.owned()])
            for b in np.nonzero(changed)[0].tolist()
        ]

    def live_refs(self):
        rows = [*self.rows.values(), *self._reserved.values()]
        return dict(collections.Counter(b for row in rows for b in row.owned()))

    def to_state(self):
        """Published rows only.  A reservation not yet published (a
        mid-flight prefill lane) is released in the pool image: restore
        requeues its request, which re-prefills it."""
        pool = self.pool
        if self._reserved:
            pool = BlockPool.from_state(pool.to_state())
            for row in self._reserved.values():
                for b in row.owned():
                    pool.release(b)
        return {
            "rows": {str(s): dataclasses.asdict(r) for s, r in self.rows.items()},
            "pool": pool.to_state(),
        }

    def from_state(self, state, caches):
        self.rows = {
            int(s): PagedRow(
                blocks=[int(b) for b in r["blocks"]],
                plen=int(r["plen"]),
                n_shared_full=int(r["n_shared_full"]),
                tail_shared=bool(r["tail_shared"]),
                cow_dst=None if r["cow_dst"] is None else int(r["cow_dst"]),
            )
            for s, r in state["rows"].items()
        }
        self._reserved = {}
        self.pool = BlockPool.from_state(state["pool"])
        self._rebaseline(caches)


def make_layout(cfg: ModelConfig, scfg: Any, stats: dict) -> KVLayout:
    """The engine's KV layout, chosen by ``scfg.kv.layout``."""
    return {"contiguous": ContiguousKV, "paged": PagedKV}[scfg.kv.layout](
        cfg, scfg, stats
    )
