"""Crash-consistent serving (serve/recovery.py): snapshot/restore, the
write-ahead journal, corruption quarantine, and substrate fallback.

The recovery contract under test: a restored engine's surviving requests
finish with outputs **bitwise identical** to a never-crashed run of the
same config — whether restore came from a snapshot + journal tail, from a
cold journal-only replay, or from an older snapshot after the newest one
was quarantined as corrupt.  Corruption that reaches a request's KV (NaN
logits, silent bit rot under checksum mode) fails exactly that request
and releases its blocks; a kernel-level decode failure falls back to the
XLA substrate once instead of killing the engine.
"""

import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.arch.model_zoo import build
from repro.configs.registry import get
from repro.serve import chaos, kvcache, recovery
from repro.serve.engine import (
    Engine,
    KVConfig,
    Request,
    RequestStatus,
    SchedulerConfig,
    ServeConfig,
)

MAX_LEN = 64
BS = 8


@pytest.fixture(scope="module")
def smol():
    cfg = get("smollm-360m-smoke")
    params = build(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _workload(cfg, n=4, seed=1, budget=10):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rng.integers(0, cfg.vocab, int(rng.integers(6, 20))).astype(
                np.int32
            ),
            budget,
            request_id=i,
        )
        for i in range(n)
    ]


def _paged(**kw):
    kw.setdefault("batch", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("block_size", BS)
    kw.setdefault("temperature", 0.8)
    kw.setdefault("seed", 3)
    return ServeConfig(**kw)


@pytest.fixture(scope="module")
def paged_oracle(smol):
    """The never-crashed ground truth every restore is compared against."""
    cfg, params = smol
    reqs = _workload(cfg)
    outs = Engine(cfg, params, _paged()).run(list(reqs))
    return reqs, {r.request_id: o.tolist() for r, o in zip(reqs, outs)}


def _drain_bitwise(eng, reqs, want):
    while eng.step():
        chaos.audit(eng)
    for r in reqs:
        res = eng.pop_result(r.request_id)
        assert res.status == RequestStatus.FINISHED, (r.request_id, res)
        assert res.tolist() == want[r.request_id], (r.request_id, res.tolist())
    if eng.pool is not None:
        assert eng.pool.free_blocks == eng.pool.num_blocks - 1, "block leak"


# ------------------------------------------------------------ journal unit --


def test_journal_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "wal_0000_00000000.jsonl")
    j = recovery.Journal(path)
    recs = [{"t": "submit", "rid": 1}, {"t": "tok", "rid": 1, "toks": [3, 4]}]
    for r in recs:
        j.append(r)
    j.close()
    assert recovery.read_journal(path) == (recs, 0)
    # crash mid-append: a half-written final line is detected and dropped
    with open(path, "ab") as f:
        f.write(b'001a2b3c {"t":"tok","rid"')
    assert recovery.read_journal(path) == (recs, 1)


def test_journal_crc_rejects_bitflip_and_everything_after(tmp_path):
    path = str(tmp_path / "wal_0000_00000000.jsonl")
    j = recovery.Journal(path)
    for i in range(3):
        j.append({"t": "tok", "rid": i, "toks": [i]})
    j.close()
    with open(path, "rb") as f:
        lines = f.read().split(b"\n")
    body = bytearray(lines[1])
    body[-2] ^= 1  # bit rot inside record 1's JSON
    lines[1] = bytes(body)
    with open(path, "wb") as f:
        f.write(b"\n".join(lines))
    recs, torn = recovery.read_journal(path)
    # record 0 survives; the flipped record AND the valid one after it are
    # dropped — order past a torn line is not trustworthy
    assert [r["rid"] for r in recs] == [0]
    assert torn == 1


# ------------------------------------------------------- restore, bitwise --


def test_snapshot_restore_replays_bitwise(smol, paged_oracle, tmp_path):
    cfg, params = smol
    reqs, want = paged_oracle
    scfg = _paged(snapshot_dir=str(tmp_path), snapshot_every=4)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    held = eng.pool.reserve(2)  # a co-tenant hold alive at crash time
    assert held
    eng.step()
    eng.recovery.wait()  # snapshot published; later steps live in the WAL
    # simulated SIGKILL: nothing closed, nothing flushed beyond the fsyncs
    eng2, report = recovery.restore_engine(cfg, params, scfg)
    assert report.source == "snapshot" and report.snapshot_key is not None
    assert report.tokens_replayed > 0
    assert recovery.replay_lag(eng2) > 0
    # the reserve holder died with the process: restore released its holds
    assert eng2.pool.external == set()
    chaos.audit(eng2)
    _drain_bitwise(eng2, reqs, want)
    assert recovery.replay_lag(eng2) == 0
    eng2.close()


def test_cold_journal_replay_and_popped_not_resurrected(
    smol, paged_oracle, tmp_path
):
    """Crash before the first snapshot: recovery is a pure journal replay
    through fresh prefill + teacher forcing.  A result the client popped
    pre-crash must not come back."""
    cfg, params = smol
    reqs, want = paged_oracle
    scfg = _paged(snapshot_dir=str(tmp_path), snapshot_every=10_000)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    while eng.step():
        pass
    popped = eng.pop_result(0)
    assert popped.status == RequestStatus.FINISHED
    eng2, report = recovery.restore_engine(cfg, params, scfg)
    assert report.source == "cold" and report.snapshot_key is None
    assert report.pops == 1 and report.resubmitted == len(reqs)
    assert eng2.status(0) == RequestStatus.UNKNOWN, "popped result came back"
    chaos.audit(eng2)
    while eng2.step():
        chaos.audit(eng2)
    for r in reqs[1:]:
        res = eng2.pop_result(r.request_id)
        assert res.status == RequestStatus.FINISHED
        assert res.tolist() == want[r.request_id]
    assert eng2.pool.free_blocks == eng2.pool.num_blocks - 1
    eng2.close()


def test_corrupt_snapshot_quarantined_older_one_used(
    smol, paged_oracle, tmp_path
):
    cfg, params = smol
    reqs, want = paged_oracle
    scfg = _paged(snapshot_dir=str(tmp_path), snapshot_every=2)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(7):
        eng.step()
    eng.recovery.wait()
    keys = recovery._snapshot_keys(str(tmp_path))
    assert len(keys) >= 2
    assert chaos.corrupt_newest_snapshot(str(tmp_path))
    eng2, report = recovery.restore_engine(cfg, params, scfg)
    assert report.quarantined, "corrupt snapshot was not quarantined"
    assert report.source == "snapshot" and report.snapshot_key == keys[-2]
    assert any(
        n.endswith(".corrupt") for n in os.listdir(tmp_path)
    ), "quarantined snapshot should stay on disk for forensics"
    chaos.audit(eng2)
    _drain_bitwise(eng2, reqs, want)
    eng2.close()


def test_chained_crash_restores_bitwise(smol, paged_oracle, tmp_path):
    """Crash, restore, crash again mid-replay, restore again: the second
    generation's anchor snapshot must make the chain self-contained."""
    cfg, params = smol
    reqs, want = paged_oracle
    scfg = _paged(snapshot_dir=str(tmp_path), snapshot_every=3)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(4):
        eng.step()
    eng.recovery.wait()
    eng2, rep2 = recovery.restore_engine(cfg, params, scfg)
    for _ in range(3):
        eng2.step()
    eng2.recovery.wait()
    eng3, rep3 = recovery.restore_engine(cfg, params, scfg)
    assert rep3.source == "snapshot"
    assert rep3.snapshot_key[0] > (rep2.snapshot_key or (0, 0))[0], (
        "second restore should come from the restored engine's generation"
    )
    chaos.audit(eng3)
    _drain_bitwise(eng3, reqs, want)
    eng3.close()


def test_incompatible_config_rejected(smol, tmp_path):
    cfg, params = smol
    scfg = _paged(snapshot_dir=str(tmp_path), snapshot_every=2)
    eng = Engine(cfg, params, scfg)
    for r in _workload(cfg, n=2):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    eng.recovery.wait()
    eng.close()
    drifted = dataclasses.replace(scfg, seed=scfg.seed + 1)
    with pytest.raises(ValueError, match="seed"):
        recovery.restore_engine(cfg, params, drifted)


# ------------------------------------------------- corruption quarantine --


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_nan_guard_quarantines_poisoned_request(smol, layout):
    cfg, params = smol
    kw = dict(batch=4, max_len=MAX_LEN, temperature=0.8, seed=3)
    if layout == "paged":
        scfg = _paged()
    else:
        scfg = ServeConfig(decode_block=BS, **kw)
    reqs = _workload(cfg)
    want = {
        r.request_id: o.tolist()
        for r, o in zip(reqs, Engine(cfg, params, scfg).run(list(reqs)))
    }
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    slot = eng._slot_of(0)
    st = eng._slots[slot]
    assert st.emitted >= 2
    if layout == "paged":
        row = eng.kv.rows[slot]
        pos = row.plen + st.emitted - 2  # last decode-written position
        at, lanes = kvcache.token_slot(
            pos % BS, cfg.n_kv_heads, cfg.resolved_head_dim, BS
        )
        eng.caches["kpool"] = (
            eng.caches["kpool"]
            .at[:, row.blocks[pos // BS], at, lanes]
            .set(jnp.nan)
        )
    else:
        plen = len(reqs[0].prompt)
        pos = plen + st.emitted - 2
        eng.caches["k"] = eng.caches["k"].at[:, slot, pos].set(jnp.nan)
    while eng.step():
        chaos.audit(eng)
    res = eng.pop_result(0)
    assert res.status == RequestStatus.FAILED
    assert "non-finite" in res.reason
    assert eng.stats["quarantined"] == 1
    # the poisoned request's garbage token reached neither output nor peers
    assert res.tolist() == want[0][: len(res)]
    for r in reqs[1:]:
        out = eng.pop_result(r.request_id)
        assert out.status == RequestStatus.FINISHED
        assert out.tolist() == want[r.request_id]
    if eng.pool is not None:
        assert eng.pool.free_blocks == eng.pool.num_blocks - 1


def test_kv_checksum_detects_finite_corruption(smol, paged_oracle):
    """Silent bit rot that stays finite sails past the NaN guard; checksum
    mode must still catch it at the next step boundary."""
    cfg, params = smol
    reqs, want = paged_oracle
    eng = Engine(cfg, params, _paged(kv_checksum=True))
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    slot = eng._slot_of(1)
    row = eng.kv.rows[slot]
    at, lanes = kvcache.token_slot(0, cfg.n_kv_heads, cfg.resolved_head_dim, BS)
    eng.caches["vpool"] = (
        eng.caches["vpool"].at[:, row.blocks[0], at, lanes].add(1.0)
    )
    eng.step()
    assert eng.status(1) == RequestStatus.FAILED
    assert eng.stats["quarantined"] >= 1
    while eng.step():
        chaos.audit(eng)
    for r in reqs:
        res = eng.pop_result(r.request_id)
        if r.request_id == 1:
            assert res.status == RequestStatus.FAILED
        else:
            assert res.status == RequestStatus.FINISHED
            assert res.tolist() == want[r.request_id]
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1


# ------------------------------------------------------ substrate fallback --


def test_substrate_fallback_is_one_shot(smol, paged_oracle):
    cfg, params = smol
    reqs, want = paged_oracle
    eng = Engine(cfg, params, _paged(substrate_fallback=True))
    calls = {"n": 0}

    def boom(*args):
        calls["n"] += 1
        raise RuntimeError("pallas lowering exploded")

    eng._decode = boom
    for r in reqs:
        eng.submit(r)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        while eng.step():
            pass
    assert calls["n"] == 1 and eng.stats["fallbacks"] == 1
    assert any("falling back" in str(w.message) for w in caught)
    # deterministic sampling makes the fallback bitwise-invisible
    for r in reqs:
        res = eng.pop_result(r.request_id)
        assert res.status == RequestStatus.FINISHED
        assert res.tolist() == want[r.request_id]
    # the substrate budget is spent: a second kernel failure is fatal
    eng._decode = boom
    eng.submit(Request(reqs[0].prompt, 2, request_id=99))
    with pytest.raises(RuntimeError, match="exploded"):
        while eng.step():
            pass


def test_substrate_fallback_disabled_raises(smol, paged_oracle):
    cfg, params = smol
    reqs, _ = paged_oracle
    eng = Engine(cfg, params, _paged(substrate_fallback=False))

    def boom(*args):
        raise RuntimeError("pallas lowering exploded")

    eng._decode = boom
    eng.submit(reqs[0])
    with pytest.raises(RuntimeError, match="exploded"):
        while eng.step():
            pass


# ------------------------------------------- crash mid-prefill (chunked) --


def test_crash_mid_prefill_restores_bitwise(smol, tmp_path):
    """A snapshot taken while a chunked-prefill lane is mid-flight
    serializes the lane's request as requeued (zero tokens published, its
    blocks released in the persisted pool image): restore re-prefills it
    from scratch and the final output is bitwise identical to a
    never-crashed run."""
    cfg, params = smol
    kw = dict(
        batch=2, max_len=MAX_LEN, kv_layout="paged", block_size=BS,
        temperature=0.8, seed=3, prefill_chunk=BS, token_budget=BS,
    )
    reqs = [
        Request(p, 5, request_id=i)
        for i, p in enumerate(
            np.random.default_rng(7).integers(
                0, cfg.vocab, (3, 40)
            ).astype(np.int32)
        )
    ]
    want = {
        r.request_id: o.tolist()
        for r, o in zip(reqs, Engine(cfg, params, ServeConfig(**kw)).run(
            [Request(r.prompt, 5, request_id=r.request_id) for r in reqs]
        ))
    }

    scfg = ServeConfig(snapshot_dir=str(tmp_path), snapshot_every=1, **kw)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    # 40-token prompts at an 8-token budget need 5 steps per lane: two
    # steps in, a lane is guaranteed mid-flight
    eng.step()
    eng.step()
    assert eng._lane is not None, "expected a mid-flight prefill lane"
    mid_rid = eng._lane.rid
    eng.recovery.wait()  # let the armed per-step snapshot publish
    eng.recovery.journal._f.close()  # simulated SIGKILL
    del eng

    eng2, report = recovery.restore_engine(cfg, params, scfg)
    chaos.audit(eng2)
    # the lane's request came back requeued, not resurrected mid-lane
    assert eng2._lane is None
    assert eng2.status(mid_rid) == RequestStatus.WAITING
    assert len(eng2._outputs[mid_rid]) == 0
    _drain_bitwise(eng2, reqs, want)
    eng2.close()


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_snapshot_format_is_pinned(smol, layout):
    """The snapshot's on-disk keys are a contract with every snapshot
    already written: the config fingerprint, the host state (a paged
    row's fields and the pool image among it) and the npz arrays.  It is
    staged with a chunked-prefill lane mid-flight, whose request the host
    state requeues and whose blocks the pool image holds free."""
    cfg, params = smol
    kv = (
        KVConfig(layout="paged", block_size=BS)
        if layout == "paged"
        else KVConfig(decode_block=BS)
    )
    scfg = ServeConfig(
        max_len=MAX_LEN, temperature=0.8, seed=3, kv=kv,
        scheduler=SchedulerConfig(batch=2, prefill_chunk=BS, token_budget=BS),
    )
    eng = Engine(cfg, params, scfg)
    prompts = np.random.default_rng(7).integers(0, cfg.vocab, (3, 40))
    for i, p in enumerate(prompts.astype(np.int32)):
        eng.submit(Request(p, 5, request_id=i))
    for _ in range(7):
        eng.step()
    assert eng._slots and eng._lane is not None
    staged = recovery._stage(eng)
    meta = staged["meta"]
    assert list(meta) == [
        "format", "step", "n_cache_leaves", "scfg", "host", "leaves",
    ]
    assert meta["format"] == 1
    assert list(meta["scfg"]) == [
        "batch", "max_len", "temperature", "seed", "prefill_bucket",
        "matmul", "attention", "kv_layout", "block_size", "num_blocks",
        "prefix_sharing", "decode_block",
    ]
    assert meta["scfg"]["kv_layout"] == layout
    host = meta["host"]
    assert list(host) == [
        "step_no", "next_rid", "next_seq", "stalled", "stats", "free",
        "waiting", "reqs", "outputs", "slots", "rows", "pool",
    ]
    assert eng._lane.rid in host["waiting"]
    assert eng._lane.slot in host["free"]
    assert list(host["slots"]) == [str(s) for s in eng._slots]
    n = meta["n_cache_leaves"]
    assert n == len(jax.tree_util.tree_leaves(eng.caches))
    assert list(staged["arrays"]) == [f"cache_{i:04d}" for i in range(n)] + [
        "cur_tok"
    ]
    assert list(meta["leaves"]) == list(staged["arrays"])
    if layout == "contiguous":
        assert meta["scfg"]["num_blocks"] is None
        assert host["rows"] == {} and host["pool"] is None
        return
    assert meta["scfg"]["num_blocks"] == scfg.resolved_num_blocks()
    assert n == 4  # kpool, len, table, vpool
    assert list(host["rows"]) == list(host["slots"])
    for row in host["rows"].values():
        assert list(row) == [
            "blocks", "plen", "n_shared_full", "tail_shared", "cow_dst",
        ]
    pool = host["pool"]
    assert list(pool) == [
        "num_blocks", "block_size", "refcount", "free", "external", "index",
    ]
    # the image holds the published rows' blocks and nothing of the lane's
    refs = {}
    for row in host["rows"].values():
        for b in row["blocks"]:
            refs[b] = refs.get(b, 0) + 1
    image = kvcache.BlockPool.from_state(pool)
    image.assert_invariants(refs)
