"""Paged KV cache: property-based differential traces + pool invariants.

The contiguous slot engine is the paged engine's *oracle*: with the
contiguous flash-decoding KV split pinned to the paged block size
(``ServeConfig.decode_block``), both layouts run the same online-softmax
reduction over the same logical keys, so every generated token must be
**bitwise** equal.  The hypothesis-style suites here drive seeded random
traces of admit/decode/evict/backfill — mixed prompt lengths, shared
prefixes, tail-sharing CoW, capacity-starved admission — and assert that
equality plus the block-pool ownership invariants (refcounts mirror live
rows, free list + owned blocks partition the pool, the prefix index never
outlives its blocks) after every step.

Seeds are fixed so CI is reproducible; crank the trace count locally with
``FUZZ_EXAMPLES=N make test-fuzz``.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import fuzz_examples
from repro.arch.model_zoo import build
from repro.configs.registry import get
from repro.serve import kvcache
from repro.serve.engine import (
    Engine,
    KVConfig,
    Request,
    SchedulerConfig,
    ServeConfig,
)


@pytest.fixture(scope="module")
def smol():
    cfg = get("smollm-360m-smoke")
    params = build(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _paged_scfg(bs=16, batch=3, max_len=64, **kw):
    # bucketed prefill (exact for this all-global smoke model) keeps the
    # randomized traces from compiling one prefill per distinct length
    kw.setdefault("prefill_bucket", 16)
    return ServeConfig(
        batch=batch, max_len=max_len, kv_layout="paged", block_size=bs, **kw
    )


def _oracle_scfg(bs=16, batch=3, max_len=64, **kw):
    # the contiguous oracle pins its decode KV split to the paged block
    # size: identical reduction order => bitwise-comparable outputs
    kw.setdefault("prefill_bucket", 16)
    return ServeConfig(
        batch=batch, max_len=max_len, attention="flash", decode_block=bs, **kw
    )


def _random_workload(rng, cfg, n, max_len, *, share_p=0.5, prefix_pool=3):
    """Mixed random prompts; ``share_p`` of them extend one of a few shared
    prefixes (sometimes exactly — exercising tail sharing + CoW)."""
    prefixes = [
        rng.integers(0, cfg.vocab, int(rng.integers(8, max_len // 2))).astype(
            np.int32
        )
        for _ in range(prefix_pool)
    ]
    reqs = []
    for i in range(n):
        if rng.random() < share_p:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            extra = int(rng.integers(0, 6))  # 0 => identical prompt
            prompt = np.concatenate(
                [pre, rng.integers(0, cfg.vocab, extra).astype(np.int32)]
            )
        else:
            prompt = rng.integers(
                0, cfg.vocab, int(rng.integers(1, max_len - 8))
            ).astype(np.int32)
        reqs.append(
            Request(
                prompt[: max_len - 4],
                max_new_tokens=int(rng.integers(1, 10)),
                request_id=i,
            )
        )
    return reqs


def _check_pool(eng):
    eng.pool.assert_invariants(eng.kv.live_refs())


def _check_device_tables(eng):
    """The device block tables of live rows must mirror the host
    ownership (`kvcache.PagedRow.blocks`), with every entry past the reserved
    span aimed at the sink."""
    tables = np.asarray(eng.caches["table"][0])
    for slot, row in eng.kv.rows.items():
        want = np.full((tables.shape[1],), kvcache.SINK_BLOCK, np.int32)
        nb = len(row.blocks)
        want[:nb] = row.blocks
        got = tables[slot]
        # a pending CoW is the one legal divergence: the device row still
        # aims at the shared tail until before_decode repoints it
        if row.cow_dst is not None:
            lb = row.plen // eng.scfg.block_size
            want[lb] = got[lb]
        assert np.array_equal(got, want), (slot, got, want)


# ---------------------------------------------------- differential traces --


@pytest.mark.fuzz
def test_paged_matches_contiguous_oracle_fuzz(smol):
    """Randomized traces: paged engine output must be bitwise equal to the
    contiguous oracle, and the pool must be fully free after drain."""
    cfg, params = smol
    for ex in range(fuzz_examples(3)):
        rng = np.random.default_rng(100 + ex)
        bs = int(rng.choice([8, 16]))
        batch = int(rng.integers(2, 5))
        temp = float(rng.choice([0.0, 0.8]))
        reqs = _random_workload(rng, cfg, int(rng.integers(6, 12)), 64)
        kw = dict(bs=bs, batch=batch, temperature=temp, seed=int(ex))
        outs_c = Engine(cfg, params, _oracle_scfg(**kw)).run(reqs)
        paged = Engine(cfg, params, _paged_scfg(**kw))
        outs_p = paged.run(reqs)
        for i, (c, p) in enumerate(zip(outs_c, outs_p)):
            assert np.array_equal(c, p), (
                f"example {ex} request {i}: paged {p.tolist()} != "
                f"oracle {c.tolist()}"
            )
        _check_pool(paged)
        assert paged.pool.free_blocks == paged.pool.num_blocks - 1, (
            "pool not fully free after drain"
        )


@pytest.mark.fuzz
def test_pool_invariants_hold_after_every_step(smol):
    """Step-granular ownership audit: refcounts, free list, index liveness
    and the device-table mirror are checked after every engine step of a
    shared-prefix trace."""
    cfg, params = smol
    rng = np.random.default_rng(7)
    reqs = _random_workload(rng, cfg, 8, 64, share_p=0.7)
    eng = Engine(cfg, params, _paged_scfg(batch=3))
    for r in reqs:
        eng.submit(r)
    _check_pool(eng)
    steps = 0
    while eng.step():
        _check_pool(eng)
        _check_device_tables(eng)
        steps += 1
        assert steps < 500, "engine failed to drain"
    _check_pool(eng)
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1


def test_blocks_not_slots_gate_admission(smol):
    """A block-starved pool stalls admission (strict FIFO) without
    deadlock or corruption: slots stay idle while blocks are scarce, every
    request completes, outputs still match the oracle bitwise."""
    cfg, params = smol
    rng = np.random.default_rng(11)
    reqs = [
        Request(
            rng.integers(0, cfg.vocab, 20).astype(np.int32),
            max_new_tokens=8,
            request_id=i,
        )
        for i in range(5)
    ]
    # pool of 5 usable blocks @ bs=16 (cap 80 tokens) but 4 slots: at most
    # two 28-token requests (2 blocks each) can be live at once
    scfg = _paged_scfg(batch=4, max_len=64, num_blocks=6)
    paged = Engine(cfg, params, scfg)
    outs_p = paged.run(reqs)
    outs_c = Engine(cfg, params, _oracle_scfg(batch=4, max_len=64)).run(reqs)
    for c, p in zip(outs_c, outs_p):
        assert np.array_equal(c, p)
    assert paged.stats["peak_active"] <= 2 < scfg.batch
    _check_pool(paged)
    assert paged.pool.free_blocks == 5


def test_prefix_sharing_aliases_and_cow(smol):
    """Concurrent requests over one prompt: full prefix blocks alias in
    every live table (refcount == #sharers), an exact-prompt twin shares
    the partial tail block, and its first decode write resolves the
    pre-reserved copy-on-write block — after which tables diverge."""
    cfg, params = smol
    rng = np.random.default_rng(5)
    pre = rng.integers(0, cfg.vocab, 40).astype(np.int32)  # 2 full + tail 8
    eng = Engine(cfg, params, _paged_scfg(batch=3, bs=16))
    eng.submit(Request(pre.copy(), 6, request_id=0))
    eng.submit(Request(pre.copy(), 6, request_id=1))       # exact twin
    eng.submit(
        Request(
            np.concatenate([pre, rng.integers(0, cfg.vocab, 3).astype(np.int32)]),
            6,
            request_id=2,
        )
    )
    # admission only (no decode write yet): peek ownership mid-step by
    # driving admission through a zero-budget...: use step() once, which
    # admits AND decodes; so check aliasing from the recorded rows after
    # the first step — CoW has already resolved for the twin by then.
    rows_before = None

    class Snap:
        def __call__(self, rid, tok, idx, done):
            nonlocal rows_before
            if rows_before is None:
                rows_before = {
                    s: (list(r.blocks), r.tail_shared, r.cow_dst)
                    for s, r in eng.kv.rows.items()
                }

    eng.step(on_token=Snap())  # admission snapshot fires at first token
    blocks = {s: b for s, (b, _, _) in rows_before.items()}
    tails = {s: t for s, (_, t, _) in rows_before.items()}
    cows = {s: c for s, (_, _, c) in rows_before.items()}
    s0, s1, s2 = sorted(blocks)
    # full prefix blocks aliased by all three
    assert blocks[s0][:2] == blocks[s1][:2] == blocks[s2][:2]
    # the exact twin aliased the partial tail too, with a reserved CoW dst
    assert blocks[s1][2] == blocks[s0][2]
    assert tails[s1] and cows[s1] is not None
    # request 2 extends past the tail content: its tail block is private
    assert blocks[s2][2] != blocks[s0][2]
    # after the first decode step the twin's CoW resolved: private tail
    row1 = eng.kv.rows[s1]
    assert row1.cow_dst is None and not row1.tail_shared
    assert row1.blocks[2] == cows[s1]
    assert eng.pool.refcount[blocks[s0][2]] == 1  # back to creator-only
    _check_pool(eng)
    while eng.step():
        pass
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1


def test_evict_then_readmit_same_slot(smol):
    """Cancellation frees a slot mid-flight; the next admission reuses
    that same slot and must see no stale state: fresh table row, fresh
    blocks, output bitwise equal to a solo run, zero leak at drain."""
    cfg, params = smol
    eng = Engine(cfg, params, _paged_scfg(batch=2, bs=16))
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, 12).astype(np.int32) for _ in range(3)]
    eng.submit(Request(prompts[0], 12, request_id=0))
    eng.submit(Request(prompts[1], 12, request_id=1))
    eng.step()
    eng.step()
    victim_slot = next(s for s, st in eng._slots.items() if st.rid == 0)
    eng.cancel(0)
    _check_pool(eng)
    eng.submit(Request(prompts[2], 6, request_id=2))
    eng.step()
    assert next(s for s, st in eng._slots.items() if st.rid == 2) == victim_slot
    _check_pool(eng)
    _check_device_tables(eng)
    while eng.step():
        pass
    solo = Engine(cfg, params, _paged_scfg(batch=2, bs=16)).run(
        [Request(prompts[2], 6, request_id=2)]
    )[0]
    assert np.array_equal(eng.pop_result(2), solo)
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1


def test_cancel_during_pending_cow_releases_reservation(smol):
    """Adversarial interleaving: an exact-prompt twin is cancelled from
    its own first-token callback — AFTER admission reserved its CoW block
    but BEFORE ``PagedKV.before_decode`` ran.  The eviction must release both the
    shared tail reference and the pending CoW reservation, leaving the
    creator untouched."""
    cfg, params = smol
    eng = Engine(cfg, params, _paged_scfg(batch=2, bs=16))
    rng = np.random.default_rng(23)
    pre = rng.integers(0, cfg.vocab, 40).astype(np.int32)  # 2 full + tail 8
    eng.submit(Request(pre.copy(), 10, request_id=0))
    eng.step()  # request 0 active, its prompt chain registered
    eng.submit(Request(pre.copy(), 10, request_id=1))  # exact twin
    seen = {}

    def cb(rid, tok, idx, done):
        if rid == 1 and idx == 0:
            slot = next(s for s, st in eng._slots.items() if st.rid == 1)
            row = eng.kv.rows[slot]
            assert row.tail_shared and row.cow_dst is not None
            seen["cow"] = row.cow_dst
            seen["tail"] = row.blocks[2]
            from repro.serve.engine import RequestStatus

            assert eng.cancel(1) == RequestStatus.CANCELLED

    eng.step(on_token=cb)
    assert "cow" in seen, "twin admission callback never fired"
    _check_pool(eng)
    _check_device_tables(eng)
    # CoW reservation back in the free list; tail back to creator-only
    assert eng.pool.refcount[seen["cow"]] == 0
    assert eng.pool.refcount[seen["tail"]] == 1
    while eng.step():
        pass
    solo = Engine(cfg, params, _paged_scfg(batch=2, bs=16)).run(
        [Request(pre.copy(), 10, request_id=0)]
    )[0]
    assert np.array_equal(eng.pop_result(0), solo)
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1


def test_double_cancel_idempotent(smol):
    """Cancelling twice (or cancelling FINISHED/unknown ids) is a no-op
    reporting the existing terminal status — clients can fire-and-forget
    cancels without racing completions."""
    from repro.serve.engine import RequestStatus

    cfg, params = smol
    eng = Engine(cfg, params, _paged_scfg(batch=1, bs=16))
    rng = np.random.default_rng(29)
    eng.submit(Request(rng.integers(0, cfg.vocab, 8).astype(np.int32), 8, request_id=0))
    eng.submit(Request(rng.integers(0, cfg.vocab, 8).astype(np.int32), 8, request_id=1))
    eng.step()  # 0 active, 1 waiting (single slot)
    assert eng.cancel(1) == RequestStatus.CANCELLED  # waiting-state cancel
    assert eng.cancel(1) == RequestStatus.CANCELLED  # double-cancel: no-op
    assert eng.stats["cancelled"] == 1
    assert eng.cancel(0) == RequestStatus.CANCELLED  # active-state cancel
    assert eng.cancel(0) == RequestStatus.CANCELLED
    assert eng.stats["cancelled"] == 2
    assert eng.cancel(99) == RequestStatus.UNKNOWN
    _check_pool(eng)
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1
    res = eng.pop_result(0)
    assert res.status == RequestStatus.CANCELLED and len(res) >= 1
    # popped: the id is gone, a third cancel reports UNKNOWN
    assert eng.cancel(0) == RequestStatus.UNKNOWN


@pytest.mark.fuzz
def test_lifecycle_fuzz_cancel_preempt_invariants(smol):
    """The step-granular trace fuzzer, extended with lifecycle events:
    seeded random cancels (any state) and forced preemptions land between
    steps while the ownership invariants and the device-table mirror are
    audited after every step.  Survivors must match the unfaulted oracle
    bitwise; everyone else must hold an oracle prefix."""
    from repro.serve.engine import RequestStatus, TERMINAL_STATUSES

    cfg, params = smol
    for ex in range(fuzz_examples(3)):
        rng = np.random.default_rng(300 + ex)
        reqs = _random_workload(rng, cfg, 8, 64, share_p=0.6)
        kw = dict(bs=8, batch=3, temperature=0.7, seed=int(ex))
        oracle = {
            r.request_id: o.tolist()
            for r, o in zip(reqs, Engine(cfg, params, _oracle_scfg(**kw)).run(reqs))
        }
        eng = Engine(cfg, params, _paged_scfg(**kw))
        for r in reqs:
            eng.submit(r)
        steps = 0
        while eng._slots or eng._waiting:
            if rng.random() < 0.2:
                live = [
                    r.request_id
                    for r in reqs
                    if eng.status(r.request_id) not in TERMINAL_STATUSES
                ]
                if live:
                    eng.cancel(live[int(rng.integers(len(live)))])
            if rng.random() < 0.2:
                actives = [
                    r.request_id
                    for r in reqs
                    if eng.status(r.request_id) == RequestStatus.ACTIVE
                ]
                if actives:
                    eng.preempt(actives[int(rng.integers(len(actives)))])
            eng.step()
            _check_pool(eng)
            _check_device_tables(eng)
            steps += 1
            assert steps < 600, "trace failed to drain"
        assert eng.pool.free_blocks == eng.pool.num_blocks - 1, "block leak"
        for r in reqs:
            res = eng.pop_result(r.request_id)
            got, want = res.tolist(), oracle[r.request_id]
            if res.status == RequestStatus.FINISHED:
                assert got == want, (ex, r.request_id, res.preemptions)
            else:
                assert got == want[: len(got)], (ex, r.request_id, res.status)


def test_paged_flash_and_xla_substrates_agree(smol):
    """attention='flash' (backend auto) and attention='xla' (pinned gather
    twin) are substrate swaps on the paged layout, not semantics changes."""
    cfg, params = smol
    rng = np.random.default_rng(13)
    reqs = _random_workload(rng, cfg, 6, 64)
    a = Engine(cfg, params, _paged_scfg(attention="flash")).run(reqs)
    b = Engine(cfg, params, _paged_scfg(attention="xla")).run(reqs)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_oversized_request_rejected(smol):
    """A request whose worst-case KV footprint exceeds the whole pool can
    never be admitted: submit must raise instead of deadlocking the queue
    or silently shrinking the budget (which would diverge from the
    contiguous oracle)."""
    cfg, params = smol
    # 2 usable blocks @ bs=16 -> 32-token capacity
    eng = Engine(cfg, params, _paged_scfg(batch=2, max_len=64, num_blocks=3))
    with pytest.raises(ValueError, match="pool"):
        eng.submit(
            Request((np.arange(30) % cfg.vocab).astype(np.int32), 10)
        )


def test_paged_rejects_unsupported_families(smol):
    for arch in ("gemma3-12b-smoke", "rwkv6-1.6b-smoke",
                 "recurrentgemma-2b-smoke"):
        cfg = get(arch)
        with pytest.raises(ValueError, match="paged"):
            Engine(cfg, None, _paged_scfg())


def test_serveconfig_validation():
    with pytest.raises(ValueError, match="kv_layout"):
        ServeConfig(kv_layout="ring")
    with pytest.raises(ValueError, match="multiple"):
        ServeConfig(kv_layout="paged", max_len=100, block_size=16)
    scfg = ServeConfig(batch=2, max_len=64, kv_layout="paged", block_size=16)
    assert scfg.resolved_num_blocks() == 2 * 64 // 16 + 1  # + sink


# --------------------------------------------------- block-pool unit tests --


def test_block_pool_alloc_release_roundtrip():
    pool = kvcache.BlockPool(6, 4)
    a, b = pool.alloc(), pool.alloc()
    assert a != b and kvcache.SINK_BLOCK not in (a, b)
    pool.retain(a)
    pool.release(a)
    assert pool.refcount[a] == 1
    pool.release(a)
    pool.release(b)
    pool.assert_invariants({})
    assert pool.free_blocks == 5


def test_block_pool_prefix_index_lifecycle():
    pool = kvcache.BlockPool(8, 4)
    toks = list(range(10))  # 2 full blocks + tail of 2
    b0, b1, bt = pool.alloc(), pool.alloc(), pool.alloc()
    pool.register(-1, tuple(toks[0:4]), b0)
    pool.register(b0, tuple(toks[4:8]), b1)
    pool.register(b1, tuple(toks[8:10]), bt)
    assert pool.match_prefix(toks) == ([b0, b1], bt)
    assert pool.match_prefix(toks[:8]) == ([b0, b1], None)
    assert pool.match_prefix(toks[:9]) == ([b0, b1], None)  # tail != exact
    assert pool.match_prefix(toks[:4] + [99] * 4) == ([b0], None)
    # releasing a block drops its index entries (and breaks the chain)
    pool.release(b1)
    assert pool.match_prefix(toks) == ([b0], None)
    pool.release(b0)
    pool.release(bt)
    pool.assert_invariants({})


def test_block_pool_refcount_drift_detected():
    pool = kvcache.BlockPool(4, 4)
    bid = pool.alloc()
    with pytest.raises(AssertionError, match="refcount"):
        pool.assert_invariants({})  # engine claims nothing owns `bid`
    pool.release(bid)
    pool.assert_invariants({})


def test_block_pool_reserve_unreserve_accounting():
    """The external-hold contract pinned directly: partial grants when the
    pool runs dry, holds invisible to engine refs but accounted by the
    invariant check, and double-/never-reserved unreserves rejected."""
    pool = kvcache.BlockPool(6, 4)
    held = pool.reserve(3)
    assert len(held) == 3 and set(held) <= set(range(1, 6))
    assert pool.free_blocks == 2
    pool.assert_invariants({})  # external holds aren't engine-owned refs
    more = pool.reserve(10)  # drier than asked: partial grant, no raise
    assert len(more) == 2 and pool.free_blocks == 0
    assert pool.reserve(1) == []  # bone dry: empty grant
    pool.unreserve(more)
    assert pool.free_blocks == 2
    with pytest.raises(AssertionError, match="non-reserved"):
        pool.unreserve(more)  # double-unreserve must not double-free
    with pytest.raises(AssertionError, match="non-reserved"):
        pool.unreserve([kvcache.SINK_BLOCK])  # sink is never reservable
    engine_owned = pool.alloc()
    with pytest.raises(AssertionError, match="non-reserved"):
        pool.unreserve([engine_owned])  # engine refs can't exit via holds
    pool.release(engine_owned)
    pool.unreserve(held)
    assert pool.free_blocks == 5
    pool.assert_invariants({})


def test_block_pool_state_roundtrip_json():
    """to_state/from_state rebuild refcounts, free order, external holds,
    and the radix index — through a JSON encode, since the recovery
    manifest embeds the state as JSON."""
    import json

    pool = kvcache.BlockPool(8, 4)
    a, b = pool.alloc(), pool.alloc()
    pool.register(-1, (1, 2, 3, 4), a)
    pool.register(a, (5, 6), b)
    pool.retain(b)
    pool.reserve(2)
    clone = kvcache.BlockPool.from_state(
        json.loads(json.dumps(pool.to_state()))
    )
    assert clone.refcount == pool.refcount
    assert clone.free == pool.free  # order matters: pop() parity
    assert clone.external == pool.external
    assert clone.index == pool.index
    assert clone.match_prefix([1, 2, 3, 4, 5, 6]) == ([a], b)
    clone.assert_invariants({a: 1, b: 2})


# ------------------------------------------------------- serve-engine fuzz --


@pytest.mark.fuzz
def test_serve_engine_stress_no_leak_deterministic(smol):
    """Satellite stress fuzz: 200+ requests arriving in seeded random
    bursts (prefix-sharing waves included), driven through a small paged
    pool.  Asserts (a) zero block leak once drained, (b) outputs bitwise
    identical under an arrival-order permutation, (c) the pool buffers are
    donation-stable across the whole run (no silent reallocation)."""
    cfg, params = smol
    n_requests = max(200, 50 * fuzz_examples(4))
    rng = np.random.default_rng(42)
    reqs = _random_workload(rng, cfg, n_requests, 64, share_p=0.4)

    def drive(order_seed):
        order = np.random.default_rng(order_seed).permutation(len(reqs))
        eng = Engine(
            cfg, params, _paged_scfg(batch=4, bs=8, temperature=0.6, seed=9)
        )
        submitted = 0
        outs = {}
        pointers = None
        pending = list(order)
        while submitted < len(reqs) or eng._slots or eng._waiting:
            burst = int(rng.integers(0, 5))
            for _ in range(min(burst, len(pending))):
                i = pending.pop(0)
                eng.submit(reqs[i])
                submitted += 1
            progressed = eng.step()
            if pointers is None and submitted > 4:
                pointers = sorted(
                    leaf.unsafe_buffer_pointer()
                    for name in ("kpool", "vpool")
                    for leaf in [eng.caches[name]]
                )
            if not progressed and submitted == len(reqs):
                break
        assert pointers == sorted(
            leaf.unsafe_buffer_pointer()
            for name in ("kpool", "vpool")
            for leaf in [eng.caches[name]]
        ), "pool buffers were reallocated mid-run (donation broke)"
        _check_pool(eng)
        assert eng.pool.free_blocks == eng.pool.num_blocks - 1, "block leak"
        for r in reqs:
            outs[r.request_id] = eng.pop_result(r.request_id).tolist()
        assert eng.stats["admitted"] >= len(reqs)
        return outs

    a = drive(order_seed=0)
    b = drive(order_seed=1)  # different arrival order, same requests
    assert a == b, "outputs depend on arrival order"


# ------------------------------------------------------ the layout seam --


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_layout_seam_contract(smol, layout):
    """The KV layout driven through its own interface, as the engine drives
    it: reserve -> publish -> before_decode -> release (and drop).  After
    every operation the pool's refcounts equal ``live_refs()`` (paged), and
    each published row reads back bitwise equal to the same scratch row
    written directly: ``kvcache.slot_store`` of its masked row, or
    ``paged_set_row`` + ``paged_store_row_blocks``."""
    cfg, params = smol
    bs, batch, max_len, lpad, budget = 16, 3, 64, 48, 6
    kv_cfg = KVConfig(layout="paged", block_size=bs) if layout == "paged" else KVConfig()
    scfg = ServeConfig(
        max_len=max_len, scheduler=SchedulerConfig(batch=batch), kv=kv_cfg
    )
    stats = dict.fromkeys(
        ["prompt_tokens", "prefix_hit_tokens", "decode_work_items", "decode_grid_full"], 0
    )
    kv = kvcache.make_layout(cfg, scfg, stats)
    paged = kv.pool is not None
    assert paged == (layout == "paged")

    def check():
        if paged:
            kv.pool.assert_invariants(kv.live_refs())

    # a bucketed prefill of two prompts right-padded to 48: A holds 2 full
    # blocks and a 5-token tail, B one block and a 4-token tail
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (37, 20)]
    toks = np.zeros((2, lpad), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    _, scratch = build(cfg).prefill(
        params, jnp.asarray(toks), kvcache.build_caches(cfg, 2, max_len),
        last_index=jnp.asarray([len(p) - 1 for p in prompts], jnp.int32),
    )
    # slot -> (scratch row, prompt); slot 2 is A's exact twin, which
    # aliases every block of A's prompt
    admits = {0: (0, prompts[0]), 1: (1, prompts[1]), 2: (0, prompts[0])}
    caches = kv.init_caches()
    check()

    hits = {}
    for slot, (_, p) in admits.items():
        meta = kv.reserve(slot, p, budget)
        kv.register(slot, p)
        hits[slot] = meta.get("hit_tokens")
        check()
    assert hits == ({0: 0, 1: 0, 2: 37} if paged else {0: None, 1: None, 2: None})
    packed = {}
    for slot, (row, p) in admits.items():
        caches, meta = kv.publish(caches, slot, scratch, row, len(p))
        packed[slot] = meta.get("packed")
        check()
    assert packed == ({0: 3, 1: 2, 2: 0} if paged else {0: None, 1: None, 2: None})
    assert sorted(kv.rows) == ([0, 1, 2] if paged else [])

    # the direct writes the published rows must equal, bitwise, and each
    # slot's prompt K/V read back against its scratch row
    if paged:
        want = kvcache.build_paged_caches(cfg, batch, max_len, kv.pool.num_blocks, bs)
        kv_only = {"k": scratch["k"], "v": scratch["v"]}
        for slot, (row, p) in admits.items():
            blocks = kv.rows[slot].blocks
            table = np.full((max_len // bs,), kvcache.SINK_BLOCK, np.int32)
            table[: len(blocks)] = blocks
            want = kvcache.paged_set_row(want, slot, jnp.asarray(table), len(p))
            if slot < 2:
                n = -(-len(p) // bs)
                want = kvcache.paged_store_row_blocks(
                    want, kv_only, row, 0, jnp.asarray(blocks[:n], jnp.int32)
                )
        for slot, (row, p) in admits.items():
            n = -(-len(p) // bs)
            for name in ("k", "v"):
                got = kvcache.unpack_blocks(
                    caches[name + "pool"][:, np.asarray(kv.rows[slot].blocks[:n])],
                    cfg.n_kv_heads, cfg.resolved_head_dim,
                ).reshape(cfg.n_layers, n * bs, cfg.n_kv_heads, -1)
                assert np.array_equal(got[:, : len(p)], scratch[name][:, row, : len(p)])
    else:
        axes = kvcache.slot_axes(cfg, max_len)
        want = kvcache.build_caches(cfg, batch, max_len)
        for slot, (row, p) in admits.items():
            small = kvcache.take_slot(scratch, row, axes)
            small = kvcache.mask_prompt_tail(small, len(p))
            want = kvcache.slot_store(want, small, slot, axes)
            for name in ("k", "v"):
                assert np.array_equal(
                    caches[name][:, slot, : len(p)], scratch[name][:, row, : len(p)]
                )
            assert (np.asarray(caches["pos"])[:, slot, len(p) :] == 10**9).all()
    jax.tree.map(np.testing.assert_array_equal, caches, want)

    # the twin's first divergent write is about to land in the shared tail
    slots = {s: SimpleNamespace(emitted=1) for s in admits}
    before = caches
    caches = kv.before_decode(caches, slots)
    check()
    work = kv.decode_work(slots)
    if paged:
        tail0, tail2 = kv.rows[0].blocks[2], kv.rows[2].blocks[2]
        assert tail2 != tail0 and kv.rows[2].cow_dst is None
        assert kv.pool.refcount[tail0] == 1
        assert np.array_equal(caches["kpool"][:, tail2], caches["kpool"][:, tail0])
        assert np.asarray(caches["table"])[0, 2, 2] == tail2
        # each row reads its blocks through the position its token lands at
        assert work == {"work": 3 + 2 + 3}
        assert stats["decode_work_items"] == 8
    else:
        assert caches is before and work == {}
        assert stats["decode_work_items"] == 0

    for slot in admits:
        caches = kv.release(caches, slot)
        check()
    # a reservation that never publishes is dropped without device writes
    assert kv.reserve(1, prompts[0], budget) is not None
    check()
    kv.drop(1)
    check()
    assert kv.rows == {} and kv.live_refs() == {}
    if paged:
        assert kv.pool.free_blocks == kv.pool.num_blocks - 1
        assert not np.asarray(caches["table"]).any()
        assert not np.asarray(caches["len"]).any()
        assert stats["prompt_tokens"] == 37 + 20 + 37 + 37
