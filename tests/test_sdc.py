"""Silent-data-corruption defense in depth (kernels/abft.py +
Engine._sdc_recover + serve/chaos.py's SDC episode layer).

Three layers of evidence:

  * a *calibration property test* for the ABFT column-checksum tolerance:
    200 seeded clean matmuls across every shipped tile config must raise
    zero false positives, while seeded single-bit flips in the exponent /
    high-mantissa range of an output's largest row element must ALL be
    caught (lower bits on bf16 outputs drown in legitimate rounding — see
    kernels/abft.py's docstring for why that boundary is physical);
  * seeded *engine episodes* (``sdc`` mark): transient compute flips ride
    the in-program fault operand and must be detected + healed by the
    oracle-substrate retry (every survivor bitwise equal to the unfaulted
    oracle); persistent KV-pool flips must quarantine exactly the owning
    request, leak-free; clean episodes must detect nothing;
  * *unlocalizable corruption*: a persistent weight flip must raise
    ``SDCUnlocalizedError`` BEFORE any poisoned token is emitted, and the
    newest-snapshot restore (with pristine params) must finish the
    workload bitwise-intact.

Default episode counts are small; ``make test-sdc`` cranks SDC_EPISODES
and CI shards the seed space via SDC_SEED.  Any failure prints its
episode seed; replay with ``SDC_EPISODES=1 SDC_SEED=<seed> make test-sdc``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sdc_episodes, sdc_seed
from repro.arch.model_zoo import build
from repro.configs.registry import get
from repro.core.mapper import MatmulTiles
from repro.kernels import abft
from repro.kernels.matmul.ops import matmul_abft
from repro.serve import chaos, recovery
from repro.serve.engine import (
    SDC_RETRY_BUDGET,
    DurabilityConfig,
    Engine,
    KernelConfig,
    KVConfig,
    Request,
    RequestStatus,
    SchedulerConfig,
    SDCUnlocalizedError,
    ServeConfig,
)

MAX_LEN = 64
BS = 8

# every tile shape the mapper's blocking search actually ships for the
# serve-path GEMM sizes (projection, MLP, unembed) — the checksum kernel's
# per-row-block granularity must calibrate at each of them
SHIPPED_TILES = [
    MatmulTiles(64, 128, 128),
    MatmulTiles(128, 128, 64),
    MatmulTiles(32, 256, 128),
    MatmulTiles(128, 64, 256),
]


@pytest.fixture(scope="module")
def smol():
    cfg = get("smollm-360m-smoke")
    params = build(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------- checksum calibration --
def _mk_operands(rng, tiles, dtype):
    """One clean seeded matmul at 2x the tile in every dim (so the kernel
    revisits row blocks and the padding paths both stay honest)."""
    m, n, k = 2 * tiles.bm, 2 * tiles.bn, 2 * tiles.bk
    a = jnp.asarray(rng.uniform(-1, 1, (m, k)).astype(np.float32)).astype(dtype)
    b = jnp.asarray(rng.uniform(-1, 1, (k, n)).astype(np.float32)).astype(dtype)
    return a, b


@pytest.mark.sdc
def test_checksum_zero_false_positives_200_clean_matmuls():
    """The calibrated tolerance must never flag a clean product: 200
    seeded matmuls cycling through every shipped tile config and both
    serve dtypes, each through the real checksum-emitting Pallas kernel."""
    for i in range(200):
        tiles = SHIPPED_TILES[i % len(SHIPPED_TILES)]
        dtype = jnp.bfloat16 if i % 2 else jnp.float32
        rng = np.random.default_rng(10_000 + i)
        a, b = _mk_operands(rng, tiles, dtype)
        out, bad = matmul_abft(a, b, tiles=tiles)
        assert not bool(bad), (
            f"false positive: clean matmul flagged (seed={10_000 + i}, "
            f"tiles={tiles}, dtype={dtype.__name__})"
        )
        assert out.dtype == dtype and out.shape == (a.shape[0], b.shape[1])


@pytest.mark.sdc
def test_checksum_catches_injected_bit_flips():
    """Single-bit flips on an output row's largest element, across the
    exponent and high-mantissa range, must ALL break the checksum: f32
    bits 20..30 (high mantissa through exponent MSB) and bf16-surviving
    bits 23..29.  Targeting the max element is what the seeded harness
    does too — magnitude-decreasing flips on tiny elements sit below the
    output dtype's own rounding noise and are physically undetectable."""
    missed = []
    for i in range(60):
        tiles = SHIPPED_TILES[i % len(SHIPPED_TILES)]
        dtype = jnp.bfloat16 if i % 2 else jnp.float32
        bits = range(23, 30) if dtype == jnp.bfloat16 else range(20, 31)
        rng = np.random.default_rng(20_000 + i)
        a, b = _mk_operands(rng, tiles, dtype)
        out = np.asarray(
            (a.astype(jnp.float32) @ b.astype(jnp.float32)).astype(dtype)
        )
        row = int(rng.integers(out.shape[0]))
        col = int(np.argmax(np.abs(out[row].astype(np.float32))))
        bit = int(rng.choice(list(bits)))
        u = np.float32(out[row, col]).view(np.uint32) ^ np.uint32(1 << bit)
        flipped = np.array(out)
        flipped[row, col] = u.view(np.float32).astype(out.dtype)
        verdict = abft.mm_check(
            jnp.asarray(np.asarray(a)), jnp.asarray(np.asarray(b)),
            jnp.asarray(flipped),
        )
        if not bool(verdict):
            missed.append((20_000 + i, str(tiles), dtype.__name__, bit))
    assert not missed, f"undetected injected flips: {missed}"


# --------------------------------------------------------- engine setup --
def _sdc_pair(cfg, params, mode, **kernel_extra):
    common = dict(max_len=MAX_LEN, temperature=0.7, seed=5)
    sched = SchedulerConfig(batch=3, prefill_bucket=16, stall_patience=6)
    eng = Engine(
        cfg,
        params,
        ServeConfig(
            scheduler=sched,
            kv=KVConfig(layout="paged", block_size=BS),
            kernel=KernelConfig(abft=mode, **kernel_extra),
            **common,
        ),
    )
    oracle_eng = Engine(
        cfg,
        params,
        ServeConfig(
            scheduler=SchedulerConfig(batch=3, prefill_bucket=16),
            kv=KVConfig(decode_block=BS),
            **common,
        ),
    )
    return eng, oracle_eng


# ------------------------------------------------------- seeded episodes --
@pytest.mark.sdc
def test_sdc_episode_matrix(smol):
    """Seeded bit-flip episodes across both abft modes; the per-episode
    fault mix cycles deterministically so every surface (compute flip, KV
    flip, mixed, clean) fires regardless of the episode count."""
    cfg, params = smol
    setups = [
        ("checksum", *_sdc_pair(cfg, params, "checksum")),
        ("paranoid", *_sdc_pair(cfg, params, "paranoid")),
    ]
    # (n_compute, n_kv) per episode — explicit so a 2-episode default run
    # still exercises both fault surfaces
    mixes = [(1, 1), (2, 1), (1, 2), (0, 1), (2, 0), (1, 1)]
    n = sdc_episodes(4)
    base = sdc_seed()
    reports = []
    for ep in range(n):
        mode, eng, oracle_eng = setups[ep % len(setups)]
        n_compute, n_kv = mixes[ep % len(mixes)]
        seed = base + chaos.SEED_STRIDE + ep
        rng = np.random.default_rng(seed)
        reqs = chaos.make_sdc_workload(rng, cfg.vocab, MAX_LEN)
        oracle = chaos.oracle_outputs(oracle_eng, reqs)
        reports.append(
            chaos.run_sdc_episode(
                eng, oracle, reqs, seed, n_compute=n_compute, n_kv=n_kv
            )
        )
    fired_compute = sum(r.injected["compute"] for r in reports)
    fired_kv = sum(r.injected["kv"] for r in reports)
    assert fired_compute > 0, "no compute fault ever fired"
    assert fired_kv > 0, "no KV flip ever fired"
    # 100% detection: run_sdc_episode asserts the per-episode ledger;
    # re-assert the aggregate so a silently-skipped episode can't hide
    assert sum(r.detected for r in reports) == fired_compute
    assert sum(r.quarantined for r in reports) == fired_kv
    assert sum(r.statuses.get("FINISHED", 0) for r in reports) > 0, (
        "no request ever survived an SDC episode"
    )


@pytest.mark.sdc
def test_sdc_clean_episode_zero_false_positives(smol):
    """A fault-free episode through the armed pipeline must detect,
    retry, and quarantine NOTHING — and (via the driver's oracle
    comparison) serve tokens bitwise identical to the unarmed engine."""
    cfg, params = smol
    eng, oracle_eng = _sdc_pair(cfg, params, "checksum")
    seed = sdc_seed() + chaos.SEED_STRIDE + 777
    rng = np.random.default_rng(seed)
    reqs = chaos.make_sdc_workload(rng, cfg.vocab, MAX_LEN)
    oracle = chaos.oracle_outputs(oracle_eng, reqs)
    rep = chaos.run_sdc_episode(eng, oracle, reqs, seed, n_compute=0, n_kv=0)
    assert rep.detected == 0 and rep.retried == 0 and rep.quarantined == 0
    assert rep.statuses == {"FINISHED": len(reqs)}


@pytest.mark.sdc
def test_sdc_retry_budget_exhaustion_quarantines(smol):
    """Repeated detections charge every live slot (a step-level checksum
    cannot name the victim row); the (SDC_RETRY_BUDGET+1)-th detection
    must quarantine the survivors as the probable corruption source
    instead of retrying forever."""
    cfg, params = smol
    eng, oracle_eng = _sdc_pair(cfg, params, "checksum")
    rng = np.random.default_rng(99)
    reqs = [
        Request(
            rng.integers(0, cfg.vocab, 12).astype(np.int32),
            max_new_tokens=24,
            request_id=i,
        )
        for i in range(2)
    ]
    oracle = chaos.oracle_outputs(oracle_eng, reqs)
    for r in reqs:
        eng.submit(r)
    eng.step()  # admit + populate the trace probe
    n_mm = eng._abft_probe["mms"]
    for hit in range(SDC_RETRY_BUDGET + 1):
        assert eng._slots, "victims finished before the budget ran out"
        eng.arm_fault(abft.FAULT_MATMUL, n_mm - 1, 0, -1, 27)
        eng.step()
        chaos.audit(eng)
        eng.step()  # one clean step between hits
        chaos.audit(eng)
    assert eng.stats["sdc_detected"] == SDC_RETRY_BUDGET + 1
    assert eng.stats["sdc_retried"] == SDC_RETRY_BUDGET + 1
    assert eng.stats["quarantined"] == len(reqs)
    while eng.step():
        chaos.audit(eng)
    assert eng.pool.free_blocks == eng.pool.num_blocks - 1
    for r in reqs:
        res = eng.pop_result(r.request_id)
        assert res.status == RequestStatus.FAILED
        assert res.reason == "sdc: retry budget exhausted"
        # every token emitted before quarantine came from a healed step
        assert res.tolist() == oracle[r.request_id][: len(res)]


@pytest.mark.sdc
def test_sdc_weight_corruption_raises_then_restores(smol, tmp_path):
    """Persistent weight rot is unlocalizable by construction (both sides
    of the checksum identity use the corrupt operand): the weight
    fingerprint must raise SDCUnlocalizedError BEFORE the step emits or
    journals anything, and restoring from the newest snapshot with
    pristine params must finish every request bitwise-intact."""
    cfg, params = smol
    common = dict(max_len=MAX_LEN, temperature=0.7, seed=5)
    scfg = ServeConfig(
        scheduler=SchedulerConfig(batch=3, prefill_bucket=16),
        kv=KVConfig(layout="paged", block_size=BS),
        kernel=KernelConfig(abft="checksum"),
        durability=DurabilityConfig(
            snapshot_dir=str(tmp_path / "snaps"),
            snapshot_every=2,
            snapshot_keep=2,
        ),
        **common,
    )
    oracle_eng = Engine(
        cfg,
        params,
        ServeConfig(
            scheduler=SchedulerConfig(batch=3, prefill_bucket=16),
            kv=KVConfig(decode_block=BS),
            **common,
        ),
    )
    rng = np.random.default_rng(41)
    reqs = [
        Request(
            rng.integers(0, cfg.vocab, 10).astype(np.int32),
            max_new_tokens=16,
            request_id=i,
        )
        for i in range(3)
    ]
    oracle = chaos.oracle_outputs(oracle_eng, reqs)
    eng = Engine(cfg, params, scfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(5):  # past snapshot_every: a snapshot has published
        eng.step()
        chaos.audit(eng)
    assert eng._slots, "workload drained before the flip landed"
    eng.params, leaf = chaos.flip_weight_bit(eng.params, rng)
    with pytest.raises(SDCUnlocalizedError, match="weight fingerprint"):
        eng.step()
    assert eng.stats["sdc_detected"] == 1
    # simulated operator response: abandon the poisoned process (journal
    # bytes survive, fd dropped) and restore with freshly loaded params
    eng.recovery.wait()
    eng.recovery.journal._f.close()
    del eng
    eng2, report = recovery.restore_engine(cfg, params, scfg)
    chaos.audit(eng2)
    assert report.source in ("snapshot", "cold")
    while eng2.step():
        chaos.audit(eng2)
    assert eng2.pool.free_blocks == eng2.pool.num_blocks - 1
    for r in reqs:
        res = eng2.pop_result(r.request_id)
        assert res.status == RequestStatus.FINISHED, (
            f"rid {r.request_id}: {res.status} ({res.reason!r})"
        )
        assert res.tolist() == oracle[r.request_id], (
            f"rid {r.request_id} diverged after weight-corruption restore"
        )
    eng2.close()


# ------------------------------------------------------------ guardrails --
def test_arm_fault_requires_abft(smol):
    cfg, params = smol
    eng = Engine(
        cfg,
        params,
        ServeConfig(
            scheduler=SchedulerConfig(batch=2),
            kv=KVConfig(layout="paged", block_size=BS),
            max_len=MAX_LEN,
        ),
    )
    with pytest.raises(ValueError, match="abft"):
        eng.arm_fault(abft.FAULT_MATMUL, 0, 0, -1, 27)
    eng.close()


def test_abft_requires_paged_layout():
    with pytest.raises(ValueError, match="paged"):
        ServeConfig(kernel=KernelConfig(abft="checksum"), max_len=MAX_LEN)


def test_abft_mode_validated():
    with pytest.raises(ValueError, match="abft"):
        KernelConfig(abft="extra-paranoid")


def test_scrub_every_validated():
    with pytest.raises(ValueError, match="scrub_every"):
        KernelConfig(scrub_every=0)


@pytest.mark.sdc
def test_weight_scrub_cadence_catches_flip_within_period(smol):
    """At ``scrub_every=N`` the full weight-fingerprint pass runs on every
    N-th step only: a weight flip landing between scrubs must still raise
    SDCUnlocalizedError within N steps (the amortization trades detection
    latency, never detection)."""
    cfg, params = smol
    scrub = 3
    eng, oracle_eng = _sdc_pair(cfg, params, "checksum", scrub_every=scrub)
    oracle_eng.close()
    rng = np.random.default_rng(4242)
    reqs = [
        Request(
            rng.integers(0, cfg.vocab, 10).astype(np.int32),
            max_new_tokens=40,
            request_id=i,
        )
        for i in range(2)
    ]
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.params, _leaf = chaos.flip_weight_bit(eng.params, rng)
    steps = 0
    with pytest.raises(SDCUnlocalizedError):
        for _ in range(2 * scrub):
            steps += 1
            eng.step()
    assert steps <= scrub, (
        f"weight flip took {steps} steps to surface at scrub_every={scrub}"
    )
    eng.close()


# ----------------------------------------------------- cost-model parity --
def test_abft_cost_batched_matches_scalar():
    """The blocking sweep's vectorized ABFT surcharge
    (costmodel.BatchedCostModel.abft_energy_pj) must agree exactly with
    the scalar pricing (energy.abft_matmul_cost) — the two encode the
    same fused-checksum accounting in different files."""
    import random

    from repro.core.costmodel import BatchedCostModel
    from repro.core.energy import CostTable, abft_energy_pj, abft_matmul_cost
    from repro.core.loopnest import matmul_nest
    from repro.core.schedule import MemLevel, Schedule

    def splits(rng, bound, n):
        out, rem = [], bound
        for _ in range(n - 1):
            f = rng.choice([d for d in range(1, rem + 1) if rem % d == 0])
            out.append(f)
            rem //= f
        out.append(rem)
        return tuple(out)

    rng = random.Random(77)
    levels = (
        MemLevel("RF", None, double_buffered=False, per_pe=True),
        MemLevel("BUF", None),
        MemLevel("DRAM", None),
    )
    table = CostTable.for_levels(levels)
    for _ in range(10):
        M = rng.choice([32, 64, 96, 128])
        N = rng.choice([64, 128, 256])
        K = rng.choice([64, 128, 256])
        nest = matmul_nest("mm", M=M, N=N, K=K)
        scheds = [
            Schedule(
                nest=nest,
                levels=levels,
                tiling={
                    d: splits(rng, nest.bounds[d], 3) for d in nest.dims
                },
                order=tuple(
                    tuple(rng.sample(list(nest.dims), len(nest.dims)))
                    for _ in range(3)
                ),
            )
            for _ in range(4)
        ]
        cm = BatchedCostModel(nest, levels)
        til, _ = cm.pack(scheds)
        got = cm.abft_energy_pj(til)
        m_i = cm.dims.index("M")
        for j in range(len(scheds)):
            t_outer = max(int(til[j, -1, m_i]), 1)
            bm = max(-(-M // t_outer), 1)
            want = abft_energy_pj(abft_matmul_cost(M, N, K, bm), table)
            assert got[j] == want


def test_checksum_engine_on_the_paged_kernel_flags_no_dead_row(smol, monkeypatch):
    """The armed attention check recomputes sampled rows on the XLA twin,
    dead rows too.  An evicted row, dead for more than a block of steps,
    and a slot never used must read the same keys in the Pallas kernel
    (interpret mode) as in the twin, so a clean run detects nothing."""
    from repro.kernels.flash_attention import ops

    paged = ops.decode_attention_paged

    def pallas_paged(*args, impl=None, interpret=None, **kw):
        if impl is None:
            impl, interpret = "pallas", True
        return paged(*args, impl=impl, interpret=interpret, **kw)

    monkeypatch.setattr(ops, "decode_attention_paged", pallas_paged)
    cfg, params = smol
    eng = Engine(cfg, params, ServeConfig(
        max_len=MAX_LEN,
        scheduler=SchedulerConfig(batch=3, prefill_bucket=16),
        kv=KVConfig(layout="paged", block_size=BS),
        kernel=KernelConfig(abft="checksum"),
    ))
    rng = np.random.default_rng(12)
    short, long = (
        Request(rng.integers(0, cfg.vocab, n).astype(np.int32),
                max_new_tokens=budget, request_id=i)
        for i, (n, budget) in enumerate([(5, 2), (10, 3 * BS)])
    )
    eng.submit(short)
    eng.submit(long)
    steps = 0
    while eng.step():
        steps += 1
    assert eng.stats["sdc_detected"] == 0 and eng.stats["quarantined"] == 0
    assert steps > 2 * BS
    for r in (short, long):
        assert eng.pop_result(r.request_id).status == RequestStatus.FINISHED
    # dead rows keep length 0: the step grows only the rows that are live
    assert np.asarray(eng.caches["len"]).tolist() == [[0, 0, 0]] * cfg.n_layers
