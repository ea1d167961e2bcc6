"""Ragged flash-decoding differential tests.

The Pallas kernel body runs in interpret mode on CPU (so CI exercises the
real kernel, not just its jnp twin) against three oracles: the dense ragged
reference, `arch.attention.dense_attention` with the serve engine's
position-mask recipe (global and sliding-window ring caches), and the
`decode_attention_xla` while-loop twin that CPU serving uses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.arch.attention import dense_attention
from repro.kernels.flash_attention.decode_attention import (
    decode_attention_paged_xla,
    decode_attention_xla,
    paged_work_list,
)
from repro.kernels.flash_attention.ops import (
    decode_attention,
    decode_attention_paged,
    flash_attention,
)
from repro.kernels.flash_attention.ref import (
    decode_attention_paged_ref,
    decode_attention_ref,
)
from repro.serve import kvcache

KEY = jax.random.PRNGKey(0)
# paged cases read one layer of stacked pools; every layer holds other
# keys, so a wrong layer offset shows
N_LAYERS, LAYER = 3, 1


def _qkv(B, S, KV, G, d, dtype=jnp.float32):
    q = jax.random.normal(KEY, (B, KV, G, d), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, d), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, d), dtype)
    return q, k, v


def _tol(dtype):
    return (
        dict(rtol=2e-2, atol=2e-2)
        if dtype == jnp.bfloat16
        else dict(rtol=2e-5, atol=2e-5)
    )


# --------------------------------------------------- kernel vs dense oracle


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bk", [8, 16, 32])  # 4 / 2 / 1 kv splits
def test_decode_kernel_ragged_lengths(bk, dtype):
    B, S, KV, G, d = 4, 32, 2, 2, 16
    q, k, v = _qkv(B, S, KV, G, d, dtype)
    lengths = jnp.asarray([1, 7, 13, 32], jnp.int32)
    want = decode_attention_ref(q, k, v, lengths)
    got = decode_attention(
        q, k, v, lengths, bk=bk, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )


@pytest.mark.parametrize("KV,G", [(1, 1), (1, 4), (2, 2), (3, 1), (2, 4)])
def test_decode_kernel_gqa_ratios(KV, G):
    """KV heads are indexed inside the kernel — every grouping ratio must
    agree with the reference (which broadcasts explicitly)."""
    B, S, d = 3, 24, 8
    q, k, v = _qkv(B, S, KV, G, d)
    lengths = jnp.asarray([3, 24, 11], jnp.int32)
    want = decode_attention_ref(q, k, v, lengths)
    got = decode_attention(
        q, k, v, lengths, bk=8, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_decode_xla_twin_matches_kernel():
    """The while-loop jnp twin (the CPU serving substrate) computes the
    same blocked recurrence as the kernel body."""
    B, S, KV, G, d = 3, 64, 2, 2, 16
    q, k, v = _qkv(B, S, KV, G, d)
    lengths = jnp.asarray([5, 40, 64], jnp.int32)
    a = decode_attention(q, k, v, lengths, bk=16, impl="pallas",
                         interpret=True)
    b = decode_attention_xla(q, k, v, lengths, bk=16)
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_decode_batched_bitwise_equals_solo(impl):
    """Slot isolation at the kernel level: a row's output must be bitwise
    identical whether it decodes alone or batched with longer rows (dead
    blocks contribute exactly zero) — the property the serve engine's
    solo-vs-batched determinism suite rests on."""
    B, S, KV, G, d = 3, 32, 2, 2, 16
    q, k, v = _qkv(B, S, KV, G, d)
    lengths = jnp.asarray([4, 19, 32], jnp.int32)
    kw = dict(bk=8, impl=impl, interpret=(impl == "pallas") or None)
    batched = decode_attention(q, k, v, lengths, **kw)
    for i in range(B):
        solo = decode_attention(
            q[i : i + 1], k[i : i + 1], v[i : i + 1], lengths[i : i + 1], **kw
        )
        assert np.array_equal(np.asarray(solo[0]), np.asarray(batched[i])), i


# ------------------------------------------- vs the serve-engine mask recipe


def test_decode_matches_engine_mask_global():
    """Global-attention slot cache: ragged length == the engine's
    causal + empty-sentinel position mask (dense_attention oracle)."""
    B, S, KV, G, d = 3, 16, 2, 2, 8
    q, k, v = _qkv(B, S, KV, G, d)
    lengths = jnp.asarray([2, 9, 16], jnp.int32)  # live slots incl. new tok
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    k_pos = jnp.where(idx < lengths[:, None], idx, 10**9)  # empty sentinel
    q_pos = lengths[:, None] - 1                           # current token
    want = dense_attention(
        q[:, None].transpose(0, 1, 2, 3, 4).reshape(B, 1, KV, G, d),
        k, v, q_pos=q_pos, k_pos=k_pos, causal=True,
    )[:, 0]
    got = decode_attention(
        q, k, v, lengths, bk=8, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("new_len", [3, 8, 13, 20])
def test_decode_matches_engine_mask_sliding_window_ring(new_len):
    """Sliding-window ring cache (size == window): the ring invariant
    slot(p) = p % size makes the single ragged bound equivalent to the
    causal + window mask over the ring's absolute positions."""
    W = 8  # ring size == window
    B, KV, G, d = 1, 2, 2, 8
    q, k, v = _qkv(B, W, KV, G, d)
    # absolute position living in each ring slot after new_len writes
    slots = np.full((W,), 10**9, np.int64)
    for p in range(new_len):
        slots[p % W] = p
    k_pos = jnp.asarray(slots[None, :], jnp.int32)
    q_pos = jnp.asarray([[new_len - 1]], jnp.int32)
    want = dense_attention(
        q[:, None].reshape(B, 1, KV, G, d), k, v,
        q_pos=q_pos, k_pos=k_pos, causal=True, window=W,
    )[:, 0]
    lengths = jnp.asarray([min(new_len, W)], jnp.int32)
    got = decode_attention(
        q, k, v, lengths, bk=4, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


# ------------------------------------------------- paged (block-table) path


def _paged_inputs(rng, B, KV, G, d, bs, n_blk, num_blocks, *, alias=False):
    """Random stacked pools, packed as the serve engine packs them, plus
    per-row block tables.  Physical order is shuffled (a row's chain is
    non-monotonic in pool order) and, with ``alias=True``, rows share
    leading blocks like prefix-cached requests do."""
    q = jax.random.normal(KEY, (B, KV, G, d))
    r = kvcache.tokens_per_row(KV, d, bs)
    blocks = (N_LAYERS, num_blocks, bs, KV, d)
    kpool = kvcache.pack_blocks(
        jax.random.normal(jax.random.fold_in(KEY, 1), blocks), r
    )
    vpool = kvcache.pack_blocks(
        jax.random.normal(jax.random.fold_in(KEY, 2), blocks), r
    )
    if alias:
        shared = rng.permutation(num_blocks)[: n_blk // 2]
        tables = np.stack([
            np.concatenate([
                shared,
                rng.permutation(
                    [b for b in range(num_blocks) if b not in shared]
                )[: n_blk - len(shared)],
            ])
            for _ in range(B)
        ])
    else:
        tables = np.stack(
            [rng.permutation(num_blocks)[:n_blk] for _ in range(B)]
        )
    return q, kpool, vpool, jnp.asarray(tables, jnp.int32)


def _dense_of(pool, tables, KV, d):
    """A row's logical keys out of layer LAYER of a packed pool:
    (B, n_blk * bs, KV, d)."""
    blocks = kvcache.unpack_blocks(pool[LAYER], KV, d)    # (N, bs, KV, d)
    return jnp.take(blocks, tables, axis=0).reshape(tables.shape[0], -1, KV, d)


@pytest.mark.parametrize(
    "KV,d,bs,r",
    [(5, 64, 128, 2), (8, 128, 128, 1), (2, 16, 8, 4), (1, 8, 8, 8),
     (3, 8, 4, 4), (2, 16, 2, 2)],
)
def test_tokens_per_row(KV, d, bs, r):
    """A packed row is whole 128-lane tiles where the block allows it:
    smollm's (5, 64) packs two tokens into 640 lanes, (8, 128) needs no
    packing, and small test shapes get a divisor of the block size."""
    got = kvcache.tokens_per_row(KV, d, bs)
    assert got == r
    assert bs % got == 0
    if got == 128 // np.gcd(KV * d, 128):
        assert (got * KV * d) % 128 == 0


@pytest.mark.parametrize("KV,d,bs", [(5, 64, 128), (8, 128, 16), (2, 16, 8), (3, 8, 4)])
def test_pack_unpack_round_trip(KV, d, bs):
    """Packing is a pure reshape of (bs, KV, d): token t, head h, dim e sits
    at row t // r, lane (t % r) * KV * d + h * d + e, and unpacking gives
    the blocks back bitwise; token_slot names the same lanes."""
    r = kvcache.tokens_per_row(KV, d, bs)
    x = jax.random.normal(KEY, (2, 3, bs, KV, d)).astype(jnp.bfloat16)
    packed = kvcache.pack_blocks(x, r)
    assert packed.shape == (2, 3, bs // r, r * KV * d)
    assert np.array_equal(
        np.asarray(kvcache.unpack_blocks(packed, KV, d)), np.asarray(x)
    )
    xn, pn = np.asarray(x, np.float32), np.asarray(packed, np.float32)
    for t in (0, bs // 2 + 1, bs - 1):
        row, lanes = kvcache.token_slot(t, KV, d, bs)
        assert np.array_equal(pn[1, 2, row, lanes], xn[1, 2, t].reshape(-1))
        h, e = KV - 1, d - 1
        assert pn[1, 2, t // r, (t % r) * KV * d + h * d + e] == xn[1, 2, t, h, e]


@pytest.mark.parametrize("KV,G", [(1, 1), (1, 4), (2, 2), (3, 1), (2, 4)])
def test_paged_kernel_gqa_vs_dense_oracle(KV, G):
    """Random non-monotonic block tables, every GQA grouping: the kernel
    body (interpret mode) must agree with the gather-then-dense oracle."""
    B, d, bs, n_blk = 3, 8, 8, 4
    rng = np.random.default_rng(0)
    q, kpool, vpool, tables = _paged_inputs(rng, B, KV, G, d, bs, n_blk, 12)
    lengths = jnp.asarray([3, 17, 32], jnp.int32)
    want = decode_attention_paged_ref(q, kpool, vpool, tables, lengths, LAYER)
    got = decode_attention_paged(
        q, kpool, vpool, tables, lengths, LAYER, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [None, 3, 8, 21])
@pytest.mark.parametrize("bs", [4, 8, 16])  # 8 / 4 / 2 kv splits
def test_paged_kernel_windows_and_splits(window, bs):
    """Sliding windows x split counts against the dense oracle (logical
    index == absolute position: the window masks the oldest keys)."""
    B, KV, G, d = 2, 2, 2, 16
    n_blk = 32 // bs
    rng = np.random.default_rng(1)
    q, kpool, vpool, tables = _paged_inputs(rng, B, KV, G, d, bs, n_blk, 10)
    lengths = jnp.asarray([5, 29], jnp.int32)
    want = decode_attention_paged_ref(
        q, kpool, vpool, tables, lengths, LAYER, window=window
    )
    got = decode_attention_paged(
        q, kpool, vpool, tables, lengths, LAYER, window=window,
        impl="pallas", interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_paged_aliased_prefix_blocks_rows_agree():
    """Rows aliasing the same leading physical blocks (prefix sharing)
    read them through their own tables: each row must match the oracle,
    and rows with identical logical content must agree bitwise."""
    B, KV, G, d, bs, n_blk = 4, 2, 2, 8, 8, 4
    rng = np.random.default_rng(2)
    q, kpool, vpool, tables = _paged_inputs(
        rng, B, KV, G, d, bs, n_blk, 12, alias=True
    )
    # rows 2 and 3: same query, same table, same length -> bitwise twins
    q = q.at[3].set(q[2])
    tables = tables.at[3].set(tables[2])
    lengths = jnp.asarray([7, 25, 13, 13], jnp.int32)
    want = decode_attention_paged_ref(q, kpool, vpool, tables, lengths, LAYER)
    got = decode_attention_paged(
        q, kpool, vpool, tables, lengths, LAYER, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    assert np.array_equal(np.asarray(got[2]), np.asarray(got[3]))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_paged_twin_matches_kernel_and_contiguous(impl):
    """The gather twin computes the kernel's recurrence, and both equal the
    contiguous twin bitwise at bk == block_size on the same logical keys —
    the property the serve engine's paged-vs-contiguous oracle rests on."""
    B, KV, G, d, bs, n_blk = 3, 2, 2, 16, 8, 4
    rng = np.random.default_rng(3)
    q, kpool, vpool, tables = _paged_inputs(rng, B, KV, G, d, bs, n_blk, 16)
    lengths = jnp.asarray([2, 19, 32], jnp.int32)
    kw = dict(impl=impl, interpret=(impl == "pallas") or None)
    got = decode_attention_paged(q, kpool, vpool, tables, lengths, LAYER, **kw)
    k_dense = _dense_of(kpool, tables, KV, d)
    v_dense = _dense_of(vpool, tables, KV, d)
    dense_twin = decode_attention_xla(q, k_dense, v_dense, lengths, bk=bs)
    if impl == "xla":
        assert np.array_equal(np.asarray(got), np.asarray(dense_twin))
        assert np.array_equal(
            np.asarray(got),
            np.asarray(
                decode_attention_paged_xla(
                    q, kpool, vpool, tables, lengths, LAYER
                )
            ),
        )
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(dense_twin), rtol=1e-6, atol=1e-6
        )


def _np_work(lens, bs, n_blk):
    """The work list counted row by row: a row reads its blocks through
    its clamped length."""
    items = []
    for b, n in enumerate(lens):
        n_b = -(-min(max(n, 1), n_blk * bs) // bs)
        items += [b * n_blk + j for j in range(n_b)]
    return items


def _sink_tables(rng, dead, n_blk, num_blocks):
    """Live rows own distinct blocks past the sink; dead rows point every
    logical block at the sink, as an evicted engine row does."""
    owned = rng.permutation(np.arange(1, num_blocks))
    return jnp.asarray(np.stack([
        np.full((n_blk,), kvcache.SINK_BLOCK)
        if is_dead else owned[b * n_blk:(b + 1) * n_blk]
        for b, is_dead in enumerate(dead)
    ]), jnp.int32)


@pytest.mark.parametrize(
    "lens,dead",
    [
        ([3, 17, 9, 30], [0, 0, 0, 0]),        # ragged live rows
        ([12, 1, 1, 1], [0, 1, 1, 1]),         # dead rows, as the decode passes them
        ([12, 32, 0, 25], [0, 1, 1, 1]),       # sink rows with lengths of their own
        ([32, 5, 32], [0, 0, 1]),              # a row at full length
        ([19], [0]),                           # one row
        ([1], [1]),                            # one dead row: all tail
        ([32, 32], [0, 0]),                    # no tail: every block live
    ],
    ids=[
        "ragged", "dead", "sink-lengths", "full-row", "one-row",
        "one-dead-row", "no-tail",
    ],
)
def test_paged_work_list_walks_live_blocks(lens, dead):
    """The work list holds each row's blocks through its length, rows in
    order, its tail padded with the last item; the kernel walking it
    matches the XLA twin on every row, the rows that point at the sink
    too, which is what the armed attention check compares."""
    B, KV, G, d, bs, n_blk = len(lens), 2, 2, 16, 8, 4
    rng = np.random.default_rng(6)
    q, kpool, vpool, _ = _paged_inputs(rng, B, KV, G, d, bs, n_blk, B * n_blk + 1)
    tables = _sink_tables(rng, dead, n_blk, B * n_blk + 1)
    lengths = jnp.asarray(lens, jnp.int32)
    assert np.array_equal(np.asarray(tables[:, 0] == kvcache.SINK_BLOCK), dead)

    work, n_work = paged_work_list(tables, lengths, bs)
    want = _np_work(lens, bs, n_blk)
    assert work.shape == (B * n_blk,) and work.dtype == jnp.int32
    assert int(n_work) == len(want)
    assert np.asarray(work)[: len(want)].tolist() == want
    assert (np.asarray(work)[len(want):] == want[-1]).all()

    got = decode_attention_paged(
        q, kpool, vpool, tables, lengths, LAYER, work=(work, n_work),
        impl="pallas", interpret=True,
    )
    twin = decode_attention_paged_xla(q, kpool, vpool, tables, lengths, LAYER)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(twin), rtol=2e-5, atol=2e-5
    )


# the served cells' heads: (kv heads, query heads a kv head serves, width);
# smollm-360m packs two tokens to a pool row, granite-8b one
SERVED_HEADS = {"smollm-360m": (5, 3, 64), "granite-8b": (8, 4, 128)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "lens,dead,window",
    [
        # a sink row at length 0, one key, a ragged row over two blocks, a
        # full row over all three: first and later items of a row
        ([0, 1, 200, 384], [1, 0, 0, 0], None),
        # a window narrower than a block, over rows of one to three blocks
        ([384, 130, 0, 257], [0, 0, 1, 0], 150),
    ],
    ids=["ragged-sink-full", "window"],
)
@pytest.mark.parametrize("heads", sorted(SERVED_HEADS))
def test_paged_kernel_at_served_head_shapes(heads, lens, dead, window, dtype):
    """The kernel at each served cell's head shapes and block of 128 tokens
    (smollm's two token parities a pool row, granite's one), against the
    XLA twin on the same stacked pools: every row, sink rows included, and
    rows whose later items reuse the query laid out at their first."""
    KV, G, d = SERVED_HEADS[heads]
    B, bs, n_blk = len(lens), 128, 3
    rng = np.random.default_rng(8)
    q, kpool, vpool, _ = _paged_inputs(rng, B, KV, G, d, bs, n_blk, B * n_blk + 1)
    assert kvcache.tokens_per_row(KV, d, bs) == (2 if d == 64 else 1)
    q, kpool, vpool = (x.astype(dtype) for x in (q, kpool, vpool))
    tables = _sink_tables(rng, dead, n_blk, B * n_blk + 1)
    lengths = jnp.asarray(lens, jnp.int32)
    got = decode_attention_paged(
        q, kpool, vpool, tables, lengths, LAYER, window=window,
        impl="pallas", interpret=True,
    )
    twin = decode_attention_paged_xla(
        q, kpool, vpool, tables, lengths, LAYER, window=window
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(twin, np.float32), **_tol(dtype)
    )


# ---------------------------------------------------------- compile economy


def test_decode_lengths_do_not_recompile():
    """Lengths are a traced scalar-prefetch operand: one compilation must
    serve every ragged length."""
    B, S, KV, G, d = 2, 32, 1, 2, 8
    q, k, v = _qkv(B, S, KV, G, d)

    fn = jax.jit(
        lambda q, k, v, lens: decode_attention(
            q, k, v, lens, bk=8, impl="pallas", interpret=True
        )
    )
    for a, b in [(1, 2), (7, 31), (32, 15)]:
        fn(q, k, v, jnp.asarray([a, b], jnp.int32)).block_until_ready()
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1


def test_paged_table_contents_do_not_recompile():
    """Block tables ride as a scalar-prefetch operand: remapping every
    logical block to new physical blocks (admission, CoW, eviction churn)
    must reuse the one compiled program."""
    B, KV, G, d, bs, n_blk = 2, 1, 2, 8, 8, 4
    rng = np.random.default_rng(4)
    q, kpool, vpool, tables = _paged_inputs(rng, B, KV, G, d, bs, n_blk, 12)

    fn = jax.jit(
        lambda q, kp, vp, t, lens, li: decode_attention_paged(
            q, kp, vp, t, lens, li, impl="pallas", interpret=True
        )
    )
    for seed, (a, b) in [(0, (1, 2)), (1, (7, 31)), (2, (32, 15))]:
        t = jnp.asarray(
            np.stack([
                np.random.default_rng(seed).permutation(12)[:n_blk]
                for _ in range(B)
            ]),
            jnp.int32,
        )
        fn(
            q, kpool, vpool, t, jnp.asarray([a, b], jnp.int32),
            jnp.int32(seed % N_LAYERS),
        ).block_until_ready()
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1


def test_paged_work_list_lengths_do_not_recompile():
    """The grid's length is the work list's traced length: lengths and
    sink rows that change it between calls reuse the one compiled
    program."""
    B, KV, G, d, bs, n_blk = 3, 1, 2, 8, 8, 4
    rng = np.random.default_rng(7)
    q, kpool, vpool, _ = _paged_inputs(rng, B, KV, G, d, bs, n_blk, 13)
    tables = _sink_tables(rng, [0] * B, n_blk, 13)

    def attend(q, kp, vp, t, lens, li):
        work = paged_work_list(t, lens, bs)
        return work[1], decode_attention_paged(
            q, kp, vp, t, lens, li, work=work, impl="pallas", interpret=True
        )

    fn = jax.jit(attend)
    seen = set()
    for lens, dead_row in [((1, 2, 3), None), ((32, 1, 9), 1), ((1, 8, 30), 0)]:
        t = tables if dead_row is None else tables.at[dead_row].set(kvcache.SINK_BLOCK)
        n_work, out = fn(q, kpool, vpool, t, jnp.asarray(lens, jnp.int32),
                         jnp.int32(LAYER))
        assert np.isfinite(np.asarray(out)).all()
        seen.add(int(n_work))
    assert seen == {3, 7, 6}
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1


def test_prefill_flash_traced_kv_len_no_recompile():
    """Satellite fix: flash_attention's q_offset/kv_len ride as traced
    operands — distinct cached lengths share one compiled program and
    match the per-length results bitwise."""
    B, Tq, Tk, KV, G, d = 1, 8, 64, 2, 2, 16
    q = jax.random.normal(KEY, (B, Tq, KV, G, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Tk, KV, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, Tk, KV, d))

    fn = jax.jit(
        lambda q, k, v, off, kl: flash_attention(
            q, k, v, q_offset=off, kv_len=kl, bq=8, bk=16
        )
    )
    outs = {}
    for off in (10, 30, 50):
        outs[off] = fn(
            q, k, v, jnp.int32(off), jnp.int32(off + Tq)
        ).block_until_ready()
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1
    # each traced-length result equals the eager per-length call
    for off, got in outs.items():
        want = flash_attention(
            q, k, v, q_offset=off, kv_len=off + Tq, bq=8, bk=16
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
        )
