"""Continuous-batching serve engine: slot-based KV cache + async admission.

The paper's §6.3 lesson — allocate resources to match the delivered
throughput, don't leave them idle — recurs at request granularity in
serving.  The old engine padded every request in a static batch to the
slowest prompt and the largest ``max_new_tokens``; here the decode batch is
a fixed ring of ``batch`` KV *slots* (one compiled decode program,
shape-stable forever) and requests flow through it continuously:

  * **admission**: a waiting request is prefilled into a batch-1 cache and
    scattered into a free slot (`serve/kvcache.slot_store`), interleaved
    with decode steps;
  * **decode**: every step advances *all* occupied slots by one token;
  * **eviction + backfill**: a slot frees the moment its request finishes
    and is re-admitted from the queue on the next step — no drain barrier.

Sampling keys are derived per request as ``fold_in(fold_in(seed, rid), t)``
so outputs are bitwise-deterministic for a fixed seed regardless of arrival
order or slot assignment (slot rows are computationally independent).

Request lifecycle.  Every request moves through a real state machine::

    WAITING -> ACTIVE -> FINISHED
                 |   \\-> CANCELLED | FAILED        (cancel / deadline)
                 \\-> PREEMPTED -> WAITING -> ACTIVE  (block/slot pressure)
    WAITING -> CANCELLED | FAILED | REJECTED       (cancel / deadline / shed)

  * :meth:`Engine.cancel` works in every state — dequeue if waiting,
    evict-and-release-blocks if active, no-op (idempotent) once terminal.
  * Per-request **deadlines** (``Request.deadline_steps``) are checked at
    the top of every :meth:`step`; an expired request is evicted through
    the same block-release path as cancellation and ends ``FAILED``.
  * **Preemption**: when the best waiting request outranks an active one
    and admission is starved (no free slot, or — paged — not enough free
    blocks), the lowest-priority victim's blocks are released (its table
    repointed at the sink, exactly the eviction idiom) and it is requeued.
    On re-admission its prompt is re-prefilled through the radix prefix
    index (shared-prefix blocks are aliased again) and its already
    generated tokens are *replayed* through the identical decode programs
    (teacher-forced, not re-emitted) — decode is deterministic, so the
    recovered KV state and every subsequent token are **bitwise identical**
    to the uninterrupted run.  (Replaying beats sampling from a re-prefill
    of ``prompt + generated``: prefill and decode attention use different
    softmax reduction orders, so prefill-produced KV/logits for
    decode-generated positions would not be bitwise-reproducible.)
  * **Load shedding**: ``ServeConfig.max_waiting`` bounds the queue
    (overflow submissions end ``REJECTED`` immediately), and a watchdog
    sheds the head of a queue that makes no admission progress with zero
    active slots for ``stall_patience`` consecutive steps — the engine
    degrades by rejecting loudly instead of livelocking.

``serve/chaos.py`` drives all of this under a seeded fault schedule and
audits the block-pool invariants plus bitwise oracle agreement after every
step; ``make test-chaos`` runs the episode matrix.

The decode hot loop is memory-shaped (the paper's words-per-MAC argument at
serve granularity), so both of its memory sins are fixed here:

  * **flash-decoding attention** (``ServeConfig(attention="flash")``, the
    default): single-token attention routes through the ragged Pallas
    decode kernel (``kernels/flash_attention/decode_attention``; jnp twin
    on CPU) with per-slot live lengths traced, so each slot reads
    ``ceil(len/bk)`` KV blocks instead of scanning all ``max_len`` slots
    through a broadcast mask.  ``attention="xla"`` keeps the masked
    dense/blockwise oracle as the measured baseline.
  * **donated KV caches**: ``_decode``/``_admit_group`` donate the cache
    pytree, so the per-row ring scatter updates the buffers in place — no
    per-step copy of every KV tensor (the engine always rebinds
    ``self.caches`` to the jit output; the donated input is dead).

Decode GEMMs can be routed through the Pallas matmul with tile sizes from
the paper's blocking search (``core.mapper.choose_matmul_tiles``) exactly
like ``kernels/matmul/ops.py`` — enable with ``ServeConfig(matmul="pallas")``.

The pre-continuous static-batch loop survives as :class:`StaticEngine`, the
baseline that ``benchmarks/serve_bench.py`` measures against; it follows the
same ``attention`` setting so the A/B isolates scheduling.
"""

from __future__ import annotations

import bisect
import dataclasses
import enum
import warnings
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.arch import layers as L
from repro.arch.model_zoo import build
from repro.configs.base import ModelConfig
from repro.serve import kvcache

# on_token(request_id, token, index, done)
TokenCallback = Callable[[int, int, int, bool], None]


class RequestStatus(str, enum.Enum):
    """Lifecycle states.  WAITING/ACTIVE/PREEMPTED are live; FINISHED,
    CANCELLED, FAILED and REJECTED are terminal (all blocks released, the
    accumulated tokens frozen); UNKNOWN is the answer for ids the engine
    has never seen (or whose results were already popped)."""

    WAITING = "WAITING"       # queued, not yet admitted
    PREFILLING = "PREFILLING"  # chunked prefill mid-flight: holds a slot
    ACTIVE = "ACTIVE"         # holds a slot (and, paged, blocks)
    PREEMPTED = "PREEMPTED"   # evicted mid-generation, requeued for recovery
    FINISHED = "FINISHED"     # ran to its token budget
    CANCELLED = "CANCELLED"   # Engine.cancel(); partial tokens kept
    FAILED = "FAILED"         # deadline expiry (reason says why)
    REJECTED = "REJECTED"     # load-shed: queue bound or watchdog
    UNKNOWN = "UNKNOWN"


TERMINAL_STATUSES = frozenset(
    {
        RequestStatus.FINISHED,
        RequestStatus.CANCELLED,
        RequestStatus.FAILED,
        RequestStatus.REJECTED,
    }
)


@dataclasses.dataclass
class RequestResult:
    """Typed request outcome: terminal status + the generated tokens.

    Terminal guarantees: FINISHED tokens are the full budget; CANCELLED /
    FAILED tokens are the prefix generated before eviction (bitwise equal
    to the same prefix of an unfaulted run); REJECTED generated nothing.
    Every terminal status implies all slot/block resources were released.

    The raw-array return of :meth:`Engine.pop_result` is deprecated; the
    array-like surface below (``__array__``/``tolist``/``len``/``shape``)
    keeps pre-lifecycle callers working unchanged.
    """

    status: RequestStatus
    tokens: np.ndarray
    reason: str = ""
    preemptions: int = 0
    # steps from submit to the first emitted token (None until it streams;
    # survives into the terminal result for SLO accounting)
    ttft_steps: int | None = None

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self.tokens, dtype)
        return arr.copy() if copy else arr

    def __len__(self) -> int:
        return len(self.tokens)

    def __iter__(self):
        return iter(self.tokens)

    def __getitem__(self, i):
        return self.tokens[i]

    @property
    def shape(self):
        return self.tokens.shape

    def tolist(self) -> list[int]:
        return self.tokens.tolist()

    # elementwise comparisons, so pre-lifecycle range checks like
    # ``(out >= 0).all()`` keep working on the typed result
    def __lt__(self, other):
        return np.asarray(self.tokens) < other

    def __le__(self, other):
        return np.asarray(self.tokens) <= other

    def __gt__(self, other):
        return np.asarray(self.tokens) > other

    def __ge__(self, other):
        return np.asarray(self.tokens) >= other


@dataclasses.dataclass(frozen=True, eq=False, init=False)
class Request:
    """One unit of work for :meth:`Engine.submit` — frozen, so a request
    enqueued on one thread can never be mutated under the engine.  The
    second positional slot stays the max-new-token count it has always
    been; ``max_new_tokens=`` is kept as a keyword alias so every
    pre-redesign caller survives unchanged."""

    prompt: np.ndarray           # (T,) int32
    max_new: int = 16
    # stable id for deterministic sampling; defaults to submission order
    request_id: int | None = None
    # higher priority admits first and may preempt strictly-lower-priority
    # active requests when admission is slot- or block-starved
    priority: int = 0
    # engine steps (not wall clock, so chaos/CI replays are deterministic)
    # the request may participate in before it FAILs; None = no deadline
    deadline_steps: int | None = None
    # per-request sampling seed; None inherits ServeConfig.seed (the
    # default computes bit-identical keys to the pre-redesign engine)
    seed: int | None = None
    # per-request streaming callback, invoked in addition to the step-level
    # one; not journaled (callbacks are not durable state)
    on_token: TokenCallback | None = None

    def __init__(
        self,
        prompt,
        max_new: int | None = None,
        request_id: int | None = None,
        priority: int = 0,
        deadline_steps: int | None = None,
        seed: int | None = None,
        on_token: TokenCallback | None = None,
        *,
        max_new_tokens: int | None = None,
    ):
        if max_new_tokens is not None:
            if max_new is not None:
                raise TypeError(
                    "pass the token budget positionally (max_new) or as "
                    "max_new_tokens=, not both"
                )
            max_new = max_new_tokens
        object.__setattr__(self, "prompt", prompt)
        object.__setattr__(self, "max_new", 16 if max_new is None else int(max_new))
        object.__setattr__(self, "request_id", request_id)
        object.__setattr__(self, "priority", priority)
        object.__setattr__(self, "deadline_steps", deadline_steps)
        object.__setattr__(self, "seed", seed)
        object.__setattr__(self, "on_token", on_token)

    @property
    def max_new_tokens(self) -> int:
        return self.max_new


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission + step-loop scheduling knobs (frozen; validation runs at
    construction so invalid combos fail eagerly, next to the fields)."""

    batch: int = 4               # number of KV slots (decode batch width)
    # >0: right-pad prompts to a multiple of this so monolithic prefill
    # compiles once per bucket, not once per length (global-attention
    # models only; other families fall back to exact-length prefill)
    prefill_bucket: int = 0
    # >0: token-level unified scheduler — prompts stream into KV through a
    # batch-1 scratch lane in fixed chunks of this many tokens, interleaved
    # with decode steps.  0 (default) keeps monolithic fused admission,
    # which is the chunked scheduler's bitwise differential oracle.
    prefill_chunk: int = 0
    # chunked only: max prefill tokens advanced per engine step
    # (token_budget // prefill_chunk chunks).  None = unlimited, which
    # degenerates to whole-prompt admission within one step.
    token_budget: int | None = None
    # bound the waiting queue: a submit that would exceed it is REJECTED
    # immediately (load shedding) instead of growing the queue without
    # bound.  None = unbounded.
    max_waiting: int | None = None
    # watchdog: consecutive steps with zero active slots and zero admission
    # progress (while requests wait) before the head of the queue is shed
    # REJECTED — the engine degrades loudly instead of livelocking on a
    # pool that will never free (external pressure, accounting bugs).
    stall_patience: int = 64
    # False: pure FIFO — priority ordering, priority preemption, and
    # chunk-granular prefill takeover are all disabled
    priorities: bool = True

    def __post_init__(self):
        if self.batch < 1:
            raise ValueError(f"batch (KV slot count) must be >= 1: {self.batch}")
        if self.prefill_bucket < 0:
            raise ValueError(
                f"prefill_bucket must be >= 0 (0 disables bucketing): "
                f"{self.prefill_bucket}"
            )
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = monolithic admission): "
                f"{self.prefill_chunk}"
            )
        if self.token_budget is not None:
            if self.prefill_chunk == 0:
                raise ValueError(
                    f"token_budget={self.token_budget} only takes effect "
                    f"with chunked prefill; set prefill_chunk > 0 or drop "
                    f"token_budget"
                )
            if self.token_budget < self.prefill_chunk:
                raise ValueError(
                    f"token_budget ({self.token_budget}) must cover at "
                    f"least one prefill_chunk ({self.prefill_chunk}) per "
                    f"step, or admission livelocks"
                )
        if self.max_waiting is not None and self.max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1 (or None for unbounded): "
                f"{self.max_waiting}"
            )
        if self.stall_patience < 1:
            raise ValueError(
                f"stall_patience must be >= 1 step: {self.stall_patience}"
            )


@dataclasses.dataclass(frozen=True)
class KVConfig:
    """KV-cache layout + paged-pool knobs."""

    # "contiguous": one (slots, max_len) KV ring per layer — HBM is sized
    # by the worst case.  "paged": a refcounted block pool + per-row block
    # tables (serve/kvcache.BlockPool); capacity tracks LIVE tokens,
    # prompts sharing a prefix alias physical blocks, and `batch` becomes a
    # scheduling cap instead of a memory cap.  The contiguous layout is the
    # paged engine's bitwise differential oracle.
    layout: str = "contiguous"
    # paged: tokens per physical KV block
    block_size: int = 16
    # paged: pool size per layer, INCLUDING the sink block.  None sizes the
    # pool to the contiguous layout's footprint (batch * max_len tokens)
    # plus the sink, which is what the equal-HBM benchmarks compare.
    num_blocks: int | None = None
    # paged: alias physical blocks across requests sharing a prompt prefix
    # (radix index + copy-on-write; see serve/kvcache.BlockPool)
    prefix_sharing: bool = True
    # pin the contiguous flash-decoding KV split (None = auto-tuned).  The
    # paged layout always splits at block_size; pinning the contiguous
    # oracle to the same value makes the two layouts' online-softmax
    # reductions identical, hence bitwise-comparable.
    decode_block: int | None = None

    def __post_init__(self):
        if self.layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'contiguous' or 'paged': {self.layout!r}"
            )
        if self.decode_block is not None and self.decode_block < 1:
            raise ValueError(f"decode_block must be >= 1: {self.decode_block}")
        if self.layout == "paged":
            if self.block_size < 1:
                raise ValueError(f"block_size must be >= 1: {self.block_size}")
            if self.num_blocks is not None and self.num_blocks < 2:
                raise ValueError(
                    f"num_blocks counts the sink block too, so a usable pool "
                    f"needs num_blocks >= 2: got {self.num_blocks} (or pass "
                    f"None to size the pool to the contiguous footprint)"
                )
            if (
                self.decode_block is not None
                and self.decode_block != self.block_size
            ):
                raise ValueError(
                    f"the paged layout always splits decode attention at "
                    f"block_size={self.block_size}; decode_block="
                    f"{self.decode_block} contradicts it — drop decode_block "
                    f"(it is only for pinning a CONTIGUOUS oracle) or set "
                    f"them equal"
                )
        elif self.num_blocks is not None:
            raise ValueError(
                f"num_blocks={self.num_blocks} only applies to "
                f"kv_layout='paged'; the contiguous layout is sized by "
                f"batch * max_len"
            )


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Compute-substrate routing."""

    # "xla" | "pallas": route projection GEMMs through the Pallas kernel
    # with mapper-chosen tiles (core.mapper.choose_matmul_tiles)
    matmul: str = "xla"
    # "flash" | "xla": decode-attention substrate.  "flash" (default) is
    # the ragged flash-decoding path (per-slot live lengths, KV reads
    # scale with live length); "xla" is the masked dense/blockwise oracle.
    attention: str = "flash"
    # "off" | "checksum" | "paranoid": ABFT verification of the decode
    # step (kernels/abft.py).  "checksum" column-checksums every
    # projection GEMM and fingerprints 4 sampled rows of each paged
    # decode-attention output; "paranoid" fingerprints every row.  Arms
    # the engine's detect->localize->retry->quarantine pipeline
    # (paged layout only).  Served tokens are bitwise identical to "off".
    abft: str = "off"
    # decode steps between full weight-fingerprint passes (abft modes
    # only).  Checksums cannot see weight corruption — both sides of the
    # Huang–Abraham identity use the corrupted operand — so weights get a
    # periodic scrub instead: it re-reads every parameter, which at 1
    # (every step, the default and the strictest setting) can dominate a
    # memory-bound decode step.  At N > 1 a weight flip is caught at the
    # next scrub, i.e. up to N-1 steps after it lands; compute/KV faults
    # are still detected on the very step they strike.
    scrub_every: int = 1

    def __post_init__(self):
        if self.matmul not in ("xla", "pallas"):
            raise ValueError(f"matmul must be 'xla' or 'pallas': {self.matmul!r}")
        if self.attention not in ("flash", "xla"):
            raise ValueError(
                f"attention must be 'flash' or 'xla': {self.attention!r}"
            )
        if self.abft not in ("off", "checksum", "paranoid"):
            raise ValueError(
                f"abft must be 'off', 'checksum' or 'paranoid': {self.abft!r}"
            )
        if not isinstance(self.scrub_every, int) or self.scrub_every < 1:
            raise ValueError(
                f"scrub_every must be a positive int: {self.scrub_every!r}"
            )


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Crash-consistency + corruption-defense knobs (serve/recovery.py)."""

    # a directory here arms the RecoveryManager — a crc32'd write-ahead
    # journal of submits/cancels/pops/token deltas (fsync'd once per step)
    # plus a crash-atomic snapshot of the full serving state every
    # `snapshot_every` steps, staged synchronously and published
    # tmp-dir+rename on a background thread.  restore_engine() rebuilds a
    # crashed engine with survivor outputs bitwise identical to the
    # never-crashed run.
    snapshot_dir: str | None = None
    snapshot_every: int = 32
    snapshot_keep: int = 3           # published snapshots retained by GC
    # fsync the journal every N per-step commits (submit/cancel/pop always
    # force a sync).  1 = classic WAL durability; raise it when the journal
    # lives on a slow disk and losing a few steps of tokens is acceptable.
    journal_fsync_every: int = 1
    # corruption quarantine: per-step NaN/Inf guard on decode logits — a
    # non-finite row FAILs (blocks released, survivors untouched) instead
    # of silently streaming garbage.  Costs nothing: the flag rides the
    # existing device->host token sync.
    guard_nan: bool = True
    # paged-only debug/detection mode: per-physical-block checksums
    # recomputed each step; an unexpected change in a block no live row
    # legally wrote quarantines every request referencing it (FAILED,
    # blocks released).  O(pool) device work per step — off by default.
    kv_checksum: bool = False
    # opt-in one-shot kernel-failure fallback: if the jitted decode path
    # raises (Pallas lowering/compile failure on an exotic backend),
    # rebuild it on the oracle substrate (flash -> masked xla; paged ->
    # gather twin) with a logged warning instead of dying.  Off by default:
    # a kernel failure is fatal, so a served run never hides that its
    # decode kernel did not run (stats["fallbacks"] counts the opt-in).
    substrate_fallback: bool = False

    def __post_init__(self):
        if self.snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1 step: {self.snapshot_every}"
            )
        if self.snapshot_keep < 1:
            raise ValueError(
                f"snapshot_keep must be >= 1 snapshot: {self.snapshot_keep}"
            )
        if self.journal_fsync_every < 1:
            raise ValueError(
                f"journal_fsync_every must be >= 1 commit: "
                f"{self.journal_fsync_every}"
            )


# legacy flat ServeConfig kwarg -> (sub-config attribute, field name).
# ServeConfig.__init__ routes these through dataclasses.replace on the
# matching sub-config (re-running its validation) with one
# DeprecationWarning per construction naming every flat kwarg used.
_LEGACY_FLAT = {
    "batch": ("scheduler", "batch"),
    "prefill_bucket": ("scheduler", "prefill_bucket"),
    "prefill_chunk": ("scheduler", "prefill_chunk"),
    "token_budget": ("scheduler", "token_budget"),
    "max_waiting": ("scheduler", "max_waiting"),
    "stall_patience": ("scheduler", "stall_patience"),
    "priorities": ("scheduler", "priorities"),
    "kv_layout": ("kv", "layout"),
    "block_size": ("kv", "block_size"),
    "num_blocks": ("kv", "num_blocks"),
    "prefix_sharing": ("kv", "prefix_sharing"),
    "decode_block": ("kv", "decode_block"),
    "matmul": ("kernel", "matmul"),
    "attention": ("kernel", "attention"),
    "abft": ("kernel", "abft"),
    "scrub_every": ("kernel", "scrub_every"),
    "snapshot_dir": ("durability", "snapshot_dir"),
    "snapshot_every": ("durability", "snapshot_every"),
    "snapshot_keep": ("durability", "snapshot_keep"),
    "journal_fsync_every": ("durability", "journal_fsync_every"),
    "guard_nan": ("durability", "guard_nan"),
    "kv_checksum": ("durability", "kv_checksum"),
    "substrate_fallback": ("durability", "substrate_fallback"),
}


@dataclasses.dataclass(init=False)
class ServeConfig:
    """Engine configuration: shape/sampling fields at the top level plus
    four nested sub-configs (scheduler / kv / kernel / durability).

    Backward compatibility is two-sided: every pre-redesign flat kwarg
    still constructs (``ServeConfig(block_size=32)`` routes into
    ``kv.block_size`` with a DeprecationWarning), and every flat name
    still READS (``scfg.block_size`` is a property over ``kv.block_size``)
    so fingerprints, engine internals, and user code survive unchanged.
    ``dataclasses.replace`` works with both spellings."""

    max_len: int = 256
    temperature: float = 0.0
    seed: int = 0
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig
    )
    kv: KVConfig = dataclasses.field(default_factory=KVConfig)
    kernel: KernelConfig = dataclasses.field(default_factory=KernelConfig)
    durability: DurabilityConfig = dataclasses.field(
        default_factory=DurabilityConfig
    )

    def __init__(
        self,
        max_len: int = 256,
        temperature: float = 0.0,
        seed: int = 0,
        scheduler: SchedulerConfig | None = None,
        kv: KVConfig | None = None,
        kernel: KernelConfig | None = None,
        durability: DurabilityConfig | None = None,
        **flat,
    ):
        self.max_len = max_len
        self.temperature = temperature
        self.seed = seed
        self.scheduler = scheduler if scheduler is not None else SchedulerConfig()
        self.kv = kv if kv is not None else KVConfig()
        self.kernel = kernel if kernel is not None else KernelConfig()
        self.durability = (
            durability if durability is not None else DurabilityConfig()
        )
        if flat:
            unknown = sorted(set(flat) - set(_LEGACY_FLAT))
            if unknown:
                raise TypeError(
                    f"ServeConfig got unexpected kwargs: {', '.join(unknown)}"
                )
            warnings.warn(
                f"flat ServeConfig kwarg(s) {sorted(flat)} are deprecated; "
                f"use the nested sub-configs "
                f"(scheduler=SchedulerConfig(...), kv=KVConfig(...), "
                f"kernel=KernelConfig(...), durability=DurabilityConfig(...))",
                DeprecationWarning,
                stacklevel=2,
            )
            grouped: dict[str, dict] = {}
            for name, val in flat.items():
                sub, field = _LEGACY_FLAT[name]
                grouped.setdefault(sub, {})[field] = val
            for sub, kwargs in grouped.items():
                # replace() re-runs the sub-config's __post_init__, so flat
                # construction validates exactly like nested construction
                setattr(self, sub, dataclasses.replace(getattr(self, sub), **kwargs))
        self.__post_init__()

    def __post_init__(self):
        # cross-sub-config checks live here, next to the fields they span;
        # everything field-local validates inside its own sub-config
        if self.max_len < 2:
            raise ValueError(
                f"max_len must be >= 2 (one prompt token + one generated): "
                f"{self.max_len}"
            )
        if self.kv_checksum and self.kv_layout != "paged":
            raise ValueError(
                "kv_checksum tracks per-physical-block sums, which only "
                "exist under kv_layout='paged'"
            )
        if self.abft != "off" and self.kv_layout != "paged":
            raise ValueError(
                "abft localizes corruption through the paged pool's "
                "per-block fingerprints and the paged attention twin; "
                "set kv_layout='paged' (or abft='off')"
            )
        if self.kv_layout == "paged" and self.max_len % self.block_size:
            raise ValueError(
                f"max_len {self.max_len} must be a multiple of "
                f"block_size {self.block_size}"
            )
        if self.prefill_chunk > 0 and self.max_len % self.prefill_chunk:
            raise ValueError(
                f"max_len {self.max_len} must be a multiple of "
                f"prefill_chunk {self.prefill_chunk} so the final chunk's "
                f"right-padding never overflows the scratch lane"
            )

    # ----- flat read-through aliases (pre-redesign field names) -----
    @property
    def batch(self) -> int:
        return self.scheduler.batch

    @property
    def prefill_bucket(self) -> int:
        return self.scheduler.prefill_bucket

    @property
    def prefill_chunk(self) -> int:
        return self.scheduler.prefill_chunk

    @property
    def token_budget(self) -> int | None:
        return self.scheduler.token_budget

    @property
    def max_waiting(self) -> int | None:
        return self.scheduler.max_waiting

    @property
    def stall_patience(self) -> int:
        return self.scheduler.stall_patience

    @property
    def priorities(self) -> bool:
        return self.scheduler.priorities

    @property
    def kv_layout(self) -> str:
        return self.kv.layout

    @property
    def block_size(self) -> int:
        return self.kv.block_size

    @property
    def num_blocks(self) -> int | None:
        return self.kv.num_blocks

    @property
    def prefix_sharing(self) -> bool:
        return self.kv.prefix_sharing

    @property
    def decode_block(self) -> int | None:
        return self.kv.decode_block

    @property
    def matmul(self) -> str:
        return self.kernel.matmul

    @property
    def attention(self) -> str:
        return self.kernel.attention

    @property
    def abft(self) -> str:
        return self.kernel.abft

    @property
    def snapshot_dir(self) -> str | None:
        return self.durability.snapshot_dir

    @property
    def snapshot_every(self) -> int:
        return self.durability.snapshot_every

    @property
    def snapshot_keep(self) -> int:
        return self.durability.snapshot_keep

    @property
    def journal_fsync_every(self) -> int:
        return self.durability.journal_fsync_every

    @property
    def guard_nan(self) -> bool:
        return self.durability.guard_nan

    @property
    def kv_checksum(self) -> bool:
        return self.durability.kv_checksum

    @property
    def substrate_fallback(self) -> bool:
        return self.durability.substrate_fallback

    def resolved_num_blocks(self) -> int:
        if self.num_blocks is not None:
            return self.num_blocks
        return self.batch * self.max_len // self.block_size + 1  # + sink

    @classmethod
    def from_plan_knobs(
        cls,
        knobs,
        *,
        max_len: int,
        temperature: float = 0.0,
        seed: int = 0,
        kernel: KernelConfig | None = None,
        durability: DurabilityConfig | None = None,
    ) -> "ServeConfig":
        """Map planner knobs (core/serveplan.ServeKnobs) onto the nested
        sub-configs.  Under the contiguous layout the planner's block_size
        pins the decode kernel's online-softmax split (KVConfig.decode_block)
        rather than a physical pool block."""
        if knobs.kv_layout == "paged":
            kv = KVConfig(
                layout="paged", block_size=knobs.block_size,
                num_blocks=knobs.num_blocks,
            )
        else:
            kv = KVConfig(layout="contiguous", decode_block=knobs.block_size)
        return cls(
            max_len=max_len,
            temperature=temperature,
            seed=seed,
            scheduler=SchedulerConfig(
                batch=knobs.slots,
                prefill_chunk=knobs.prefill_chunk,
                token_budget=knobs.token_budget,
            ),
            kv=kv,
            kernel=kernel,
            durability=durability,
        )

    @classmethod
    def autotune(
        cls,
        model_cfg: ModelConfig,
        *,
        max_len: int = 256,
        workload=None,
        hardware=None,
        space=None,
        kv_budget_tokens: int | None = None,
        calibration=None,
        cache: bool | str = True,
        temperature: float = 0.0,
        seed: int = 0,
        kernel: KernelConfig | None = None,
        durability: DurabilityConfig | None = None,
    ) -> "ServeConfig":
        """Build a ServeConfig from the DSE planner (core/serveplan.py):
        sweep the joint (slots, layout, block_size, num_blocks,
        prefill_chunk, token_budget) space under an iso-HBM KV budget, and
        map the winning knobs onto the nested sub-configs.  The plan itself
        is attached as ``cfg.autotune_plan`` for provenance; winners persist
        in the REPRO_SERVE_PLAN_CACHE store, so repeat constructions are a
        cache hit.  Kernel/durability choices are not planned — pass them
        through unchanged."""
        from repro.core import serveplan  # planner is numpy-only; lazy

        plan = serveplan.plan_serve(
            model_cfg,
            max_len=max_len,
            workload=workload,
            hardware=hardware,
            space=space,
            kv_budget_tokens=kv_budget_tokens,
            calibration=calibration,
            cache=cache,
        )
        cfg = cls.from_plan_knobs(
            plan.knobs,
            max_len=max_len,
            temperature=temperature,
            seed=seed,
            kernel=kernel,
            durability=durability,
        )
        cfg.autotune_plan = plan
        return cfg


@dataclasses.dataclass
class _ReqInfo:
    """Host-side record of one request, alive from submit to pop_result."""

    rid: int
    prompt: np.ndarray
    budget: int                  # effective max_new_tokens
    priority: int
    deadline: int | None         # absolute engine step number, or None
    seq: int                     # arrival order (FIFO tie-break in-priority)
    status: RequestStatus = RequestStatus.WAITING
    reason: str = ""
    preemptions: int = 0
    # resolved sampling seed (Request.seed or ServeConfig.seed) and its
    # precomputed per-request PRNG base fold_in(PRNGKey(seed), rid); the
    # jitted programs fold the step index in on device, completing the
    # legacy fold_in(fold_in(PRNGKey(seed), rid), t) chain bit-for-bit
    seed: int = 0
    key: np.ndarray | None = None
    submitted: int = 0           # engine step count at submit
    ttft: int | None = None      # steps from submit to first emitted token
    on_token: TokenCallback | None = None  # per-request stream (not journaled)


@dataclasses.dataclass
class _SlotState:
    rid: int
    emitted: int                 # tokens generated so far (this occupancy)
    budget: int                  # effective max_new_tokens
    # preemption recovery: tokens already recorded before eviction.  While
    # emitted < replay the decode loop teacher-forces the recorded tokens
    # (asserting bitwise re-derivation) without re-emitting them.
    replay: int = 0
    # abft: checksum-failed steps survived while this request was live
    # (quarantined once it exceeds SDC_RETRY_BUDGET)
    sdc_retries: int = 0


@dataclasses.dataclass
class _PagedRow:
    """Block ownership of one live paged request (host side)."""

    blocks: list[int]            # logical block -> physical, len == total
    plen: int                    # prompt tokens
    n_shared_full: int           # leading full blocks aliased via the index
    tail_shared: bool            # partial prompt tail aliased (CoW pending)
    cow_dst: int | None          # pre-allocated CoW target for the tail


@dataclasses.dataclass
class _PrefillLane:
    """One mid-flight chunked prefill: the PREFILLING request holds a slot
    (and, paged, its blocks) while its prompt streams through the batch-1
    scratch cache chunk by chunk.  Nothing is published to the shared KV
    until install time, so dropping a lane needs no device writes."""

    rid: int
    slot: int
    filled: int = 0              # prompt tokens already through the scratch
    row: _PagedRow | None = None  # paged ownership (radix-registered at install)


def _pallas_mm(x: jax.Array, w: jax.Array) -> jax.Array:
    """(..., K) @ (K, N) through the schedule-driven Pallas matmul."""
    from repro.kernels.matmul.ops import matmul

    out = matmul(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(x.shape[:-1] + (w.shape[-1],))


def _pallas_mm_abft(x: jax.Array, w: jax.Array) -> jax.Array:
    """ABFT-checked Pallas matmul: the kernel emits per-row-block column
    checksums verified in-program; the verdict joins the active
    AbftTrace's flags (the trace-level e^T check still runs on top, so
    the injected-fault path is covered on both substrates)."""
    from repro.arch import layers as L
    from repro.kernels.matmul.ops import matmul_abft

    out, bad = matmul_abft(x.reshape(-1, x.shape[-1]), w)
    trace = L._ABFT[0]
    if trace is not None:
        trace.flags.append(bad)
    return out.reshape(x.shape[:-1] + (w.shape[-1],))


# checksum-failed steps one request survives (each costs a rewind +
# oracle-substrate re-execution) before it is quarantined as the probable
# corruption source
SDC_RETRY_BUDGET = 2


class SDCUnlocalizedError(RuntimeError):
    """A detected silent-data-corruption could not be pinned to one
    request (the oracle-substrate retry still failed its checksums, or
    the weight fingerprint itself changed).  Raised BEFORE the step's
    tokens are emitted or journaled, so the newest snapshot + journal
    replay a state with no corrupt token in it: restore via
    ``recovery.restore_engine`` (with freshly loaded params) instead of
    serving wrong tokens."""


class Engine:
    """Continuous-batching engine over the model zoo's prefill/decode."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        if cfg.family == "encdec":
            raise ValueError(
                "continuous batching serves decoder-only LMs; whisper-style "
                "encdec requests need per-request encoder state"
            )
        self.cfg = cfg
        self.model = build(cfg)
        self.params = params
        self.scfg = scfg
        self._abft = scfg.abft if scfg.abft != "off" else None
        if scfg.matmul == "pallas":
            self._impl = _pallas_mm_abft if self._abft else _pallas_mm
        else:
            self._impl = None
        self._attn = "flash" if scfg.attention == "flash" else None
        self._paged = scfg.kv_layout == "paged"

        if self._paged:
            if not kvcache.supports_paged(cfg):
                raise ValueError(
                    f"kv_layout='paged' needs all-global attention; "
                    f"{cfg.name} has ring/recurrent/hybrid caches"
                )
            nb = scfg.resolved_num_blocks()
            self.caches = kvcache.build_paged_caches(
                cfg, scfg.batch, scfg.max_len, nb, scfg.block_size
            )
            self.pool = kvcache.BlockPool(nb, scfg.block_size)
            self._axes = None
        else:
            self.caches = kvcache.build_caches(cfg, scfg.batch, scfg.max_len)
            self.pool = None
            self._axes = kvcache.slot_axes(cfg, scfg.max_len)
        self._free: deque[int] = deque(range(scfg.batch))
        # waiting rids, kept sorted by (-priority, seq): head = best request.
        # Preempted requests keep their original seq, so they re-enter ahead
        # of later arrivals of the same priority.
        self._waiting: list[int] = []
        self._reqs: dict[int, _ReqInfo] = {}
        self._slots: dict[int, _SlotState] = {}
        self._rows: dict[int, _PagedRow] = {}
        self._outputs: dict[int, list[int]] = {}
        self._next_rid = 0
        self._next_seq = 0
        self._step_no = 0
        self._stalled = 0            # consecutive idle no-progress steps
        self._cur_tok = np.zeros((scfg.batch,), np.int32)
        self._seed_roots: dict[int, jax.Array] = {}  # seed -> PRNGKey(seed)
        # scheduling evidence for the iso-memory benches plus the lifecycle
        # counters the chaos harness and fault-storm bench report
        self.stats = {
            "peak_active": 0,
            "admitted": 0,
            "preempted": 0,
            "recovered": 0,
            "cancelled": 0,
            "expired": 0,
            "rejected": 0,
            "shed": 0,
            "quarantined": 0,   # corruption guard: rows FAILED mid-decode
            "fallbacks": 0,     # substrate fallbacks taken (0 or 1)
            "snapshots": 0,     # recovery snapshots staged
            "sdc_detected": 0,  # abft: steps whose checksums flagged
            "sdc_retried": 0,   # abft: oracle-substrate step re-executions
        }

        model, impl, axes = self.model, self._impl, self._axes
        max_len = scfg.max_len
        sample_one = self._sampler()

        def first_tok(logits, keys):
            # per-row base keys come in precomputed (fold_in(PRNGKey(seed),
            # rid)); folding t=0 here completes the legacy key chain bitwise
            return jax.vmap(
                lambda lg, k: sample_one(lg, jax.random.fold_in(k, jnp.int32(0)))
            )(logits, keys)

        def admit_fn(params, toks, big, slots_, keys, true_lens):
            """Fused admission: prefill `n` prompts (right-padded rows mask
            their tail; exact rows mask nothing), scatter each into its
            slot, and sample each request's first token — one dispatch."""
            n = toks.shape[0]
            small = kvcache.build_caches(cfg, n, max_len)
            with L.matmul_override(impl):
                logits, small = model.prefill(
                    params, toks, small, last_index=true_lens - 1
                )
            small = kvcache.mask_prompt_tail(small, true_lens)
            for i in range(n):
                big = kvcache.slot_store(
                    big, kvcache.take_slot(small, i, axes), slots_[i], axes
                )
            return first_tok(logits, keys), big

        def paged_prefill_fn(params, toks, keys, true_lens):
            """Paged admission, phase 1: prefill into a contiguous scratch
            (the SAME program shape the contiguous oracle admits through,
            so first tokens and packed K/V stay bitwise comparable) and
            sample each request's first token.  Phase 2 packs the scratch
            into pool blocks row by row (`kvcache.paged_store_row_blocks`),
            skipping blocks aliased from the prefix index."""
            n = toks.shape[0]
            small = kvcache.build_caches(cfg, n, max_len)
            with L.matmul_override(impl):
                logits, small = model.prefill(
                    params, toks, small, last_index=true_lens - 1
                )
            return first_tok(logits, keys), {"k": small["k"], "v": small["v"]}

        # the KV cache pytree is DONATED: the ring scatter and admission
        # slot_store update the buffers in place instead of copying every
        # KV tensor per step.  The engine immediately rebinds self.caches
        # to the jit output, so the consumed input is never read again.
        # The paged helpers follow the same contract: pack/set/CoW are
        # donated scatters into the pool, never pool copies.
        # ---- abft state (kernels/abft.py) ----
        # fault operand: one-shot transient-SDC injection point threaded
        # through the jitted decode program (zeros = disarmed; the armed
        # and disarmed programs are the same executable)
        self._fault = np.zeros((8,), np.int32)
        self._abft_probe: dict[str, int] = {}  # trace-time check counts
        self._retry_fn = None       # oracle-substrate re-execution (lazy)
        self._rewind = None         # len-rewind program (lazy)
        self._wsums0 = None
        self._colstats = None
        if self._abft:
            from repro.kernels.abft import weight_colstats, weight_sums

            # per-leaf weight fingerprints, baselined ONCE here: ABFT
            # checksums can't see weight flips (both sides of the identity
            # use the corrupted operand), so decode re-reduces and compares
            # exactly — same jitted program on every scrub, bitwise stable
            self._wsums0 = jax.jit(weight_sums)(params)
            # static per-column |w| bounds for the checksum tolerance, so
            # the per-step check never re-reads the (immutable) weights
            self._colstats = jax.jit(weight_colstats)(params)
        self._decode = self._make_decode(self._attn)
        self._fallback_done = False
        self._admit_group = jax.jit(admit_fn, donate_argnums=(2,))
        self._paged_prefill = jax.jit(paged_prefill_fn)
        self._pack_row = jax.jit(kvcache.paged_store_row_blocks, donate_argnums=(0,))
        self._set_row = jax.jit(kvcache.paged_set_row, donate_argnums=(0,))
        self._cow = jax.jit(kvcache.paged_copy_block, donate_argnums=(0,))
        if self._paged:
            self._sink_row = np.zeros((scfg.max_len // scfg.block_size,), np.int32)
        else:
            self._sink_row = None

        # ---- token-level unified scheduler (prefill_chunk > 0) ----
        # Prompts stream through a persistent batch-1 contiguous scratch
        # cache in fixed (1, prefill_chunk) chunks: positions derive from
        # the scratch's length cursor (`positions=None` in logits_fn), so
        # chunk N continues exactly where chunk N-1 stopped and the K/V/
        # logits bits match a monolithic prefill of the whole prompt.
        # Install reuses the monolithic publication paths verbatim
        # (mask_prompt_tail + slot_store, or paged set-row + pack), which
        # is what makes the prefill_chunk=0 engine a bitwise oracle.
        self._chunk = scfg.prefill_chunk
        self._lane: _PrefillLane | None = None
        self._scratch = None
        if self._chunk:
            if not kvcache.supports_padded_prefill(cfg):
                raise ValueError(
                    f"prefill_chunk needs all-global attention (positions "
                    f"derive from the cache cursor and the final chunk is "
                    f"right-padded); {cfg.name} has ring/recurrent/hybrid "
                    f"caches — use monolithic admission (prefill_chunk=0)"
                )

            def chunk_fn(params, toks, scratch, last_index, key):
                """One fixed-shape prefill chunk through the scratch lane.
                A candidate first token is sampled every chunk at
                `last_index` (vmapped over the 1-row batch, mirroring the
                admission programs bit-for-bit); only the final chunk's
                survives on the host."""
                with L.matmul_override(impl):
                    x = L.embed(params["embed"], toks)
                    logits, scratch, _ = model.logits_fn(
                        params, x, positions=None, caches=scratch
                    )
                sel = jnp.take_along_axis(
                    logits, last_index[:, None, None], axis=1
                )[:, 0]
                return first_tok(sel, key[None]), scratch

            def install_fn(big, scratch, slot, true_lens):
                """Publish a completed lane into the contiguous ring — the
                exact monolithic admission path (tail mask + slot scatter),
                so the installed slot is bitwise the monolithic one."""
                small = kvcache.mask_prompt_tail(scratch, true_lens)
                return kvcache.slot_store(
                    big, kvcache.take_slot(small, 0, axes), slot, axes
                )

            self._chunk_step = jax.jit(chunk_fn, donate_argnums=(2,))
            self._install_slot = jax.jit(install_fn, donate_argnums=(0,))
            self._fresh_scratch = jax.jit(
                lambda: kvcache.build_caches(cfg, 1, max_len)
            )

        # optional per-physical-block checksum audit (paged only): host
        # mirror of |kpool|+|vpool| sums per block, verified after every
        # step against the blocks legally written that step
        self._kv_sums: np.ndarray | None = None
        self._pool_sums = None
        self._touched: set[int] = set()
        # abft localizes inter-step KV flips through the same per-block
        # fingerprints, so it arms them even without kv_checksum
        if scfg.kv_checksum or (self._abft and self._paged):

            def pool_sums_fn(caches):
                k = jnp.sum(
                    jnp.abs(caches["kpool"].astype(jnp.float32)),
                    axis=(0, 2, 3, 4),
                )
                v = jnp.sum(
                    jnp.abs(caches["vpool"].astype(jnp.float32)),
                    axis=(0, 2, 3, 4),
                )
                return k + v

            self._pool_sums = jax.jit(pool_sums_fn)
            self._refresh_kv_sums()

        # crash consistency: journal + periodic snapshots (serve/recovery)
        self.recovery = None
        if scfg.snapshot_dir:
            from repro.serve.recovery import RecoveryManager

            RecoveryManager.attach(
                self,
                scfg.snapshot_dir,
                every=scfg.snapshot_every,
                keep=scfg.snapshot_keep,
                fsync_every=scfg.journal_fsync_every,
            )

    def _sampler(self):
        temp = self.scfg.temperature

        def sample_one(logits: jax.Array, key: jax.Array) -> jax.Array:
            if temp <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(key, logits / temp).astype(jnp.int32)

        return sample_one

    def _req_base_key(self, rid: int, seed: int) -> np.ndarray:
        """Per-request PRNG base ``fold_in(PRNGKey(seed), rid)``, computed
        once at submit.  The jitted programs fold the step index in on
        device, so with the default seed the full chain is bit-identical
        to the legacy ``fold_in(fold_in(PRNGKey(scfg.seed), rid), t)``."""
        root = self._seed_roots.get(seed)
        if root is None:
            root = self._seed_roots[seed] = jax.random.PRNGKey(seed)
        return np.asarray(jax.random.fold_in(root, rid), np.uint32)

    def _make_decode(self, attn):
        """Build the jitted decode program on substrate ``attn`` (rebuilt
        once by `_decode_call` on kernel failure).  Besides the sampled
        tokens it returns a per-row non-finite-logits flag — the
        corruption guard rides the token sync, costing no extra transfer.
        """
        model, impl, dblk = self.model, self._impl, self.scfg.decode_block
        sample_one = self._sampler()

        if self._abft:
            from repro.kernels.abft import AbftTrace, weight_sums

            from repro.kernels.abft import FAULT_SCRUB

            mode, wsums0, probe = self._abft, self._wsums0, self._abft_probe
            colstats = self._colstats

            def decode_abft_fn(params, toks, caches, keys, ts, fault):
                trace = AbftTrace(mode, fault, colstats)
                with (
                    L.matmul_override(impl),
                    L.attention_override(attn),
                    L.decode_block_override(dblk),
                    L.abft_override(trace),
                ):
                    logits, caches = model.decode_step(params, toks, caches)
                probe["mms"] = trace.mm_calls
                probe["attns"] = trace.attn_calls
                nxt = jax.vmap(
                    lambda lg, k, t: sample_one(lg, jax.random.fold_in(k, t))
                )(logits, keys, ts)
                bad = ~jnp.all(
                    jnp.isfinite(logits.astype(jnp.float32)), axis=-1
                )
                # full weight pass only on scrub steps (fault[FAULT_SCRUB],
                # set by the host on the scrub_every cadence) — it is the
                # one ABFT cost that scales with total params, not batch
                w_bad = jax.lax.cond(
                    fault[FAULT_SCRUB] != 0,
                    lambda: jnp.any(weight_sums(params) != wsums0),
                    lambda: jnp.zeros((), jnp.bool_),
                )
                flags = trace.any_bad().astype(jnp.int32) | (
                    w_bad.astype(jnp.int32) << 1
                )
                return (nxt, bad, flags), caches

            return jax.jit(decode_abft_fn, donate_argnums=(2,))

        def decode_fn(params, toks, caches, keys, ts):
            with (
                L.matmul_override(impl),
                L.attention_override(attn),
                L.decode_block_override(dblk),
            ):
                logits, caches = model.decode_step(params, toks, caches)
            nxt = jax.vmap(
                lambda lg, k, t: sample_one(lg, jax.random.fold_in(k, t))
            )(logits, keys, ts)
            bad = ~jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
            return (nxt, bad), caches

        return jax.jit(decode_fn, donate_argnums=(2,))

    def _decode_call(self, *args):
        """Run the decode program.  A failure is fatal unless the caller
        opted into ``substrate_fallback``: then it falls back ONCE to the
        oracle substrate (flash -> masked xla attend; paged -> the gather
        twin, both reached by rebuilding with ``attn=None``).
        Pallas kernel failures surface at trace/compile time — before the
        donated caches are consumed — so the retry sees intact buffers."""
        try:
            return self._decode(*args)
        except Exception as e:
            if (
                self._fallback_done
                or not self.scfg.substrate_fallback
                or self._attn is None
            ):
                raise
            warnings.warn(
                f"decode substrate {self._attn!r} failed ({type(e).__name__}: "
                f"{e}); falling back to the oracle substrate once",
                RuntimeWarning,
                stacklevel=2,
            )
            self._fallback_done = True
            self._attn = None
            self._decode = self._make_decode(None)
            self.stats["fallbacks"] += 1
            return self._decode(*args)

    def _refresh_kv_sums(self) -> None:
        """(Re)baseline the per-block checksum mirror from the current
        device pools — at init and after a snapshot restore."""
        if self._pool_sums is not None:
            self._kv_sums = np.asarray(self._pool_sums(self.caches))

    # ---------------------------------------------------------- admission --
    def submit(self, req: Request) -> int:
        """Queue a request; returns its id.  Prompts longer than
        ``max_len - 1`` keep their most recent tokens; ``max_new_tokens`` is
        truncated so the request never outgrows its slot.  A full waiting
        queue (``ServeConfig.max_waiting``) REJECTs the submission instead
        of raising — poll :meth:`status` / :meth:`pop_result`."""
        rid = req.request_id if req.request_id is not None else self._next_rid
        if rid in self._reqs:
            raise ValueError(f"duplicate request_id {rid}")
        if req.deadline_steps is not None and req.deadline_steps < 0:
            raise ValueError(
                f"request {rid}: deadline_steps must be >= 0: "
                f"{req.deadline_steps}"
            )
        self._next_rid = max(self._next_rid, rid + 1)
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        max_len = self.scfg.max_len
        if len(prompt) >= max_len:
            prompt = prompt[-(max_len - 1) :]
        budget = min(int(req.max_new_tokens), max_len - len(prompt))
        if self._paged:
            # never let one request outgrow the whole pool: its admission
            # would wait forever for blocks that can't exist (deadlock),
            # and silently shrinking the budget would quietly diverge from
            # the contiguous oracle — reject loudly instead.  With the
            # default pool sizing (batch * max_len tokens) this can never
            # trigger: the max_len truncation above already bounds
            # prompt + budget to max_len <= capacity.
            cap_tokens = (self.pool.num_blocks - 1) * self.scfg.block_size
            if len(prompt) + budget > cap_tokens:
                raise ValueError(
                    f"request {rid} needs {len(prompt) + budget} KV tokens "
                    f"but the whole pool holds {cap_tokens}; grow "
                    f"num_blocks or shorten the request"
                )
        deadline = (
            self._step_no + req.deadline_steps
            if req.deadline_steps is not None
            else None
        )
        seed = self.scfg.seed if req.seed is None else int(req.seed)
        info = _ReqInfo(
            rid=rid,
            prompt=prompt,
            budget=budget,
            priority=int(req.priority),
            deadline=deadline,
            seq=self._next_seq,
            seed=seed,
            key=self._req_base_key(rid, seed),
            submitted=self._step_no,
            on_token=req.on_token,
        )
        self._next_seq += 1
        self._reqs[rid] = info
        self._outputs[rid] = []
        if budget <= 0 or len(prompt) == 0:
            self._finish(info, RequestStatus.FINISHED, "empty prompt or budget")
        elif (
            self.scfg.max_waiting is not None
            and len(self._waiting) >= self.scfg.max_waiting
        ):
            self.stats["rejected"] += 1
            self._finish(
                info,
                RequestStatus.REJECTED,
                f"queue full (max_waiting={self.scfg.max_waiting})",
            )
        else:
            self._enqueue(info)
        if self.recovery is not None:
            # journaled AFTER the outcome is known: the record carries the
            # terminal-at-submit status too, so replay needs no re-validation
            self.recovery.record_submit(info)
        return rid

    def _enqueue(self, info: _ReqInfo) -> None:
        bisect.insort(
            self._waiting,
            info.rid,
            key=lambda r: (-self._reqs[r].priority, self._reqs[r].seq),
        )

    def _finish(self, info: _ReqInfo, status: RequestStatus, reason: str) -> None:
        info.status = status
        info.reason = reason

    def _bucket_len(self, plen: int) -> int:
        scfg = self.scfg
        bucket = (
            scfg.prefill_bucket
            if kvcache.supports_padded_prefill(self.cfg)
            else 0
        )
        lpad = -(-plen // bucket) * bucket if bucket > 0 else plen
        if lpad > scfg.max_len:
            lpad = plen  # bucket would overflow the cache: exact length
        return lpad

    def _activate(self, info: _ReqInfo, slot: int, tok: int, on_token) -> bool:
        """Shared first-token bookkeeping; returns True when the request
        stays active (budget not exhausted at admission).  A recovering
        (preempted) request replays instead of emitting: its recorded
        first token must re-derive bitwise from the fresh prefill."""
        out = self._outputs[info.rid]
        replay = len(out)
        if replay:
            assert tok == out[0], (
                f"request {info.rid}: recovery re-prefill diverged at token "
                f"0 ({tok} != recorded {out[0]})"
            )
            self.stats["recovered"] += 1
        else:
            out.append(tok)
            if info.ttft is None:
                info.ttft = self._step_no - info.submitted
        self._cur_tok[slot] = tok
        info.status = RequestStatus.ACTIVE
        # the slot is registered BEFORE the callback runs so a callback
        # that cancels/preempts (stop sequences, client disconnects) goes
        # through the ordinary ACTIVE eviction path
        self._slots[slot] = _SlotState(
            rid=info.rid, emitted=1, budget=info.budget, replay=replay
        )
        done = info.budget == 1
        if not replay:
            self._emit_cbs(info, tok, 0, done, on_token)
        if info.status != RequestStatus.ACTIVE:
            return False  # callback ended it; slot already released
        if done:
            self._release_slot(slot)
            self._finish(info, RequestStatus.FINISHED, "")
            return False
        return True

    @staticmethod
    def _emit_cbs(
        info: _ReqInfo, tok: int, idx: int, done: bool, on_token
    ) -> None:
        """Deliver one emitted token to the per-request callback (if any)
        then the step-level one; either may cancel/preempt mid-delivery —
        callers re-check status afterwards exactly as before."""
        if info.on_token is not None:
            info.on_token(info.rid, tok, idx, done)
        if on_token is not None:
            on_token(info.rid, tok, idx, done)

    @staticmethod
    def _prompt_batch(lpad: int, infos: list[_ReqInfo]) -> tuple:
        """Right-pad one admission group's prompts into a (n, lpad) token
        batch plus per-row PRNG base keys / true lengths."""
        n = len(infos)
        toks = np.zeros((n, lpad), np.int32)
        keys = np.empty((n, 2), np.uint32)
        tlens = np.empty((n,), np.int32)
        for j, info in enumerate(infos):
            toks[j, : len(info.prompt)] = info.prompt
            keys[j], tlens[j] = info.key, len(info.prompt)
        return toks, keys, tlens

    def _admit_waiting(self, on_token: TokenCallback | None) -> bool:
        """Backfill every free slot from the queue.  Admissions sharing a
        prefill length run as ONE fused jitted call (prefill + tail mask +
        slot scatter + first-token sample); right-padding to
        ``prefill_bucket`` collapses mixed prompt lengths onto one compiled
        shape where that is exact (`kvcache.supports_padded_prefill`).
        Returns True when anything was admitted."""
        if self._paged:
            return self._admit_waiting_paged(on_token)
        groups: dict[int, list[tuple[_ReqInfo, int]]] = {}
        while self._free and self._waiting:
            info = self._reqs[self._waiting.pop(0)]
            slot = self._free.popleft()
            lpad = self._bucket_len(len(info.prompt))
            groups.setdefault(lpad, []).append((info, slot))

        for lpad, items in groups.items():
            toks, keys, tlens = self._prompt_batch(lpad, [it[0] for it in items])
            slots_ = np.asarray([it[1] for it in items], np.int32)
            toks0, self.caches = self._admit_group(
                self.params,
                jnp.asarray(toks),
                self.caches,
                jnp.asarray(slots_),
                jnp.asarray(keys),
                jnp.asarray(tlens),
            )
            toks0 = np.asarray(toks0)
            self.stats["admitted"] += len(items)
            for j, (info, slot) in enumerate(items):
                self._activate(info, slot, int(toks0[j]), on_token)
        self.stats["peak_active"] = max(self.stats["peak_active"], len(self._slots))
        return bool(groups)

    # ------------------------------------------------------ paged admission --
    def _admit_waiting_paged(self, on_token: TokenCallback | None) -> bool:
        """Paged admission: a request enters when a slot AND enough free
        blocks are available (strict order over (-priority, arrival) — the
        queue head never gets jumped).  Ownership is committed host-side
        first (prefix match -> retain aliases, allocate the rest, register
        this chain), then each prefill group runs as one jitted call and
        each row's private blocks are packed into the pool."""
        scfg = self.scfg
        bs = scfg.block_size
        n_blk = scfg.max_len // bs
        groups: dict[int, list[tuple[_ReqInfo, int, _PagedRow]]] = {}
        while self._free and self._waiting:
            info = self._reqs[self._waiting[0]]
            row = self._commit_row(info)
            if row is None:
                break  # head-of-line waits for completions to free blocks
            self._waiting.pop(0)
            slot = self._free.popleft()
            # monolithic admission packs in this same step, so the chain
            # can be published to the prefix index immediately
            self._register_chain(info, row)
            self._rows[slot] = row
            if self._kv_sums is not None:
                # checksum mode: admission packs (or aliases) these blocks
                # this step; aliased prefix blocks are untouched on device
                # but marking them is a harmless over-approximation
                self._touched.update(row.blocks)
            lpad = self._bucket_len(row.plen)
            groups.setdefault(lpad, []).append((info, slot, row))

        for lpad, items in groups.items():
            toks, keys, tlens = self._prompt_batch(lpad, [it[0] for it in items])
            toks0, scratch = self._paged_prefill(
                self.params,
                jnp.asarray(toks),
                jnp.asarray(keys),
                jnp.asarray(tlens),
            )
            toks0 = np.asarray(toks0)
            self.stats["admitted"] += len(items)
            for j, (info, slot, row) in enumerate(items):
                table_row = np.full((n_blk,), kvcache.SINK_BLOCK, np.int32)
                table_row[: len(row.blocks)] = row.blocks
                self.caches = self._set_row(
                    self.caches,
                    jnp.int32(slot),
                    jnp.asarray(table_row),
                    jnp.int32(row.plen),
                )
                n_prompt = -(-row.plen // bs)
                start = row.n_shared_full
                n_pack = n_prompt - start - (1 if row.tail_shared else 0)
                if n_pack > 0:
                    self.caches = self._pack_row(
                        self.caches,
                        scratch,
                        jnp.int32(j),
                        jnp.int32(start),
                        jnp.asarray(row.blocks[start : start + n_pack], jnp.int32),
                    )
                self._activate(info, slot, int(toks0[j]), on_token)
        self.stats["peak_active"] = max(self.stats["peak_active"], len(self._slots))
        return bool(groups)

    def _commit_row(self, info: _ReqInfo) -> _PagedRow | None:
        """Host-side block ownership for one paged admission: retain prefix
        aliases, allocate the rest, reserve the CoW target (so the first
        divergent write can never be starved by admissions racing it to
        the free list).  Returns None when the pool cannot satisfy the
        request right now — nothing is committed in that case."""
        scfg = self.scfg
        bs = scfg.block_size
        prompt, budget = info.prompt, info.budget
        plen = len(prompt)
        total = -(-(plen + budget) // bs)
        shared_full: list[int] = []
        shared_tail = None
        if scfg.prefix_sharing:
            shared_full, shared_tail = self.pool.match_prefix(prompt.tolist())
        n_shared = len(shared_full) + (1 if shared_tail is not None else 0)
        cow_needed = shared_tail is not None and budget > 1
        need = total - n_shared + (1 if cow_needed else 0)
        if need > self.pool.free_blocks:
            return None
        for b in shared_full:
            self.pool.retain(b)
        if shared_tail is not None:
            self.pool.retain(shared_tail)
        blocks = list(shared_full)
        if shared_tail is not None:
            blocks.append(shared_tail)
        while len(blocks) < total:
            blocks.append(self.pool.alloc())
        cow_dst = self.pool.alloc() if cow_needed else None
        return _PagedRow(
            blocks=blocks,
            plen=plen,
            n_shared_full=len(shared_full),
            tail_shared=shared_tail is not None,
            cow_dst=cow_dst,
        )

    def _register_chain(self, info: _ReqInfo, row: _PagedRow) -> None:
        """Publish this row's prompt blocks in the radix prefix index.
        Monolithic admission does this at commit time (it packs within the
        same step); chunked admission defers it to install time — a block
        whose K/V has not been packed yet must never be aliased by a
        concurrent admission."""
        if not self.scfg.prefix_sharing:
            return
        bs = self.scfg.block_size
        toks = info.prompt.tolist()
        n_full = row.plen // bs
        prev = -1
        for i in range(n_full):
            self.pool.register(prev, tuple(toks[i * bs : (i + 1) * bs]), row.blocks[i])
            prev = row.blocks[i]
        tail = tuple(toks[n_full * bs :])
        if tail and n_full < len(row.blocks):
            self.pool.register(prev, tail, row.blocks[n_full])

    # ----------------------------------------------- chunked prefill lane --
    def _start_lane(self) -> bool:
        """Claim the queue head for the scratch lane: reserve a slot (and,
        paged, commit block ownership) and mark it PREFILLING.  Returns
        False when no request can start (empty queue, no free slot, or a
        block-starved pool)."""
        if self._lane is not None or not self._waiting or not self._free:
            return False
        info = self._reqs[self._waiting[0]]
        row = None
        if self._paged:
            row = self._commit_row(info)
            if row is None:
                return False
        self._waiting.pop(0)
        slot = self._free.popleft()
        info.status = RequestStatus.PREFILLING
        self._scratch = self._fresh_scratch()
        self._lane = _PrefillLane(rid=info.rid, slot=slot, row=row)
        return True

    def _advance_lane(self):
        """Run ONE fixed-shape chunk of the lane's prompt through the
        scratch.  Only the final chunk is right-padded (intermediate
        chunks are always full, so the scratch length cursor that derives
        positions never overshoots mid-prompt).  Returns (done, candidate
        first token)."""
        lane = self._lane
        info = self._reqs[lane.rid]
        C = self._chunk
        plen = len(info.prompt)
        end = min(plen, lane.filled + C)
        toks = np.zeros((1, C), np.int32)
        toks[0, : end - lane.filled] = info.prompt[lane.filled : end]
        li = np.asarray([min(C - 1, max(0, plen - 1 - lane.filled))], np.int32)
        tok0, self._scratch = self._chunk_step(
            self.params,
            jnp.asarray(toks),
            self._scratch,
            jnp.asarray(li),
            jnp.asarray(info.key),
        )
        lane.filled = end
        return end >= plen, tok0

    def _install_lane(self, tok0, on_token: TokenCallback | None) -> None:
        """Publish a completed lane: install the scratch K/V through the
        EXACT monolithic publication path (contiguous tail-mask + slot
        scatter, or paged set-row + block pack), register the paged chain
        in the prefix index, and activate the request with its sampled
        first token — from here on it is indistinguishable from a
        monolithically admitted request."""
        lane = self._lane
        self._lane = None
        info = self._reqs[lane.rid]
        plen = len(info.prompt)
        slot = lane.slot
        if self._paged:
            row = lane.row
            bs = self.scfg.block_size
            n_blk = self.scfg.max_len // bs
            table_row = np.full((n_blk,), kvcache.SINK_BLOCK, np.int32)
            table_row[: len(row.blocks)] = row.blocks
            self.caches = self._set_row(
                self.caches,
                jnp.int32(slot),
                jnp.asarray(table_row),
                jnp.int32(plen),
            )
            n_prompt = -(-plen // bs)
            start = row.n_shared_full
            n_pack = n_prompt - start - (1 if row.tail_shared else 0)
            if n_pack > 0:
                self.caches = self._pack_row(
                    self.caches,
                    {"k": self._scratch["k"], "v": self._scratch["v"]},
                    jnp.int32(0),
                    jnp.int32(start),
                    jnp.asarray(row.blocks[start : start + n_pack], jnp.int32),
                )
            self._register_chain(info, row)
            self._rows[slot] = row
            if self._kv_sums is not None:
                self._touched.update(row.blocks)
        else:
            self.caches = self._install_slot(
                self.caches,
                self._scratch,
                jnp.int32(slot),
                jnp.asarray([plen], jnp.int32),
            )
        self.stats["admitted"] += 1
        self._activate(info, slot, int(np.asarray(tok0)[0]), on_token)
        self.stats["peak_active"] = max(self.stats["peak_active"], len(self._slots))

    def _drop_lane(self) -> None:
        """Release a mid-flight lane's resources.  No device writes are
        needed: install is the only publisher, so the device block table
        and slot caches were never touched — the slot and any committed
        blocks simply return to their free pools."""
        lane = self._lane
        self._lane = None
        if lane.row is not None:
            for b in lane.row.blocks:
                self.pool.release(b)
            if lane.row.cow_dst is not None:
                self.pool.release(lane.row.cow_dst)
        self._free.append(lane.slot)

    def _preempt_lane(self) -> None:
        """Chunk-granular preemption: a higher-priority arrival takes the
        lane between chunks.  The victim requeues PREEMPTED at its
        original arrival position; it has emitted zero tokens, so recovery
        is a plain re-prefill (through the prefix index when paged) —
        bitwise identical by determinism."""
        info = self._reqs[self._lane.rid]
        self._drop_lane()
        info.status = RequestStatus.PREEMPTED
        info.preemptions += 1
        self.stats["preempted"] += 1
        self._enqueue(info)

    def _schedule_chunks(self, on_token: TokenCallback | None) -> bool:
        """The unified scheduler's admission half: advance up to
        ``token_budget // prefill_chunk`` chunks this step — starting,
        installing, and (priority) preempting lanes at chunk granularity —
        then fall through to the shared decode of all live slots.  Returns
        True when any admission progress was made."""
        progressed = False
        budget = self.scfg.token_budget
        chunks_left = None if budget is None else budget // self._chunk
        while chunks_left is None or chunks_left > 0:
            if (
                self._lane is not None
                and self._waiting
                and self.scfg.priorities
                and self._reqs[self._waiting[0]].priority
                > self._reqs[self._lane.rid].priority
            ):
                self._preempt_lane()
                progressed = True
            if self._lane is None and not self._start_lane():
                break
            done, tok0 = self._advance_lane()
            progressed = True
            if chunks_left is not None:
                chunks_left -= 1
            if done:
                self._install_lane(tok0, on_token)
        return progressed

    def _resolve_cow(self) -> None:
        """Before rows write: give every slot still aliasing a shared
        prompt-tail block its pre-reserved private copy (first divergent
        write is about to land at ``plen``, inside that block)."""
        for slot in sorted(self._slots):
            row = self._rows.get(slot)
            if row is None or row.cow_dst is None:
                continue
            lb = row.plen // self.scfg.block_size
            src = row.blocks[lb]
            self.caches = self._cow(
                self.caches,
                jnp.int32(slot),
                jnp.int32(lb),
                jnp.int32(src),
                jnp.int32(row.cow_dst),
            )
            self.pool.release(src)
            if self._kv_sums is not None:
                self._touched.add(row.cow_dst)
            row.blocks[lb] = row.cow_dst
            row.cow_dst = None
            row.tail_shared = False

    def _evict_paged(self, slot: int) -> None:
        """Release a finished/cancelled/preempted row: repoint its device
        table at the sink (the always-full-batch decode keeps writing
        through dead rows, and these blocks are about to be reused) and
        return every owned block — including a still-pending CoW
        reservation — to the pool."""
        row = self._rows.pop(slot)
        self.caches = self._set_row(
            self.caches,
            jnp.int32(slot),
            jnp.asarray(self._sink_row),
            jnp.int32(0),
        )
        for b in row.blocks:
            self.pool.release(b)
        if row.cow_dst is not None:
            self.pool.release(row.cow_dst)

    def _release_slot(self, slot: int) -> None:
        """Evict a live slot for any reason (finish, cancel, deadline,
        preemption): paged rows release their blocks through the sink
        repoint, and the slot returns to the free ring for backfill."""
        del self._slots[slot]
        if self._paged:
            self._evict_paged(slot)
        self._free.append(slot)

    def _slot_of(self, rid: int) -> int:
        return next(s for s, st in self._slots.items() if st.rid == rid)

    def live_block_refs(self) -> dict[int, int]:
        """Physical block -> reference count implied by live rows (the
        ground truth the pool's refcounts must mirror; used by the fuzz
        suite's invariant checks)."""
        refs: dict[int, int] = {}
        rows = list(self._rows.values())
        if self._lane is not None and self._lane.row is not None:
            rows.append(self._lane.row)  # lane ownership commits at start
        for row in rows:
            for b in row.blocks:
                refs[b] = refs.get(b, 0) + 1
            if row.cow_dst is not None:
                refs[row.cow_dst] = refs.get(row.cow_dst, 0) + 1
        return refs

    # ---------------------------------------------------------- lifecycle --
    def status(self, rid: int) -> RequestStatus:
        info = self._reqs.get(rid)
        return RequestStatus.UNKNOWN if info is None else info.status

    def cancel(self, rid: int, reason: str = "cancelled") -> RequestStatus:
        """Cancel a request in any state: dequeue if waiting/preempted,
        evict-and-release-blocks if active.  Idempotent — cancelling a
        terminal (or unknown) request changes nothing and returns its
        current status.  Partial tokens stay retrievable via
        :meth:`pop_result`."""
        info = self._reqs.get(rid)
        if info is None:
            return RequestStatus.UNKNOWN
        if info.status in TERMINAL_STATUSES:
            return info.status
        if info.status == RequestStatus.ACTIVE:
            self._release_slot(self._slot_of(rid))
        elif info.status == RequestStatus.PREFILLING:
            self._drop_lane()  # nothing published yet: just return resources
        else:  # WAITING or PREEMPTED: sitting in the queue
            self._waiting.remove(rid)
        self.stats["cancelled"] += 1
        self._finish(info, RequestStatus.CANCELLED, reason)
        if self.recovery is not None:
            self.recovery.record_cancel(rid, reason)
        return RequestStatus.CANCELLED

    def preempt(self, rid: int) -> bool:
        """Forcibly evict an ACTIVE request: its blocks are released (table
        repointed at the sink) and it is requeued as PREEMPTED at its
        original arrival position.  On re-admission the prompt re-prefills
        through the prefix index and the already-generated tokens replay
        through the identical decode programs, so the resumed output is
        bitwise identical to an uninterrupted run.  Returns False for
        non-active requests."""
        info = self._reqs.get(rid)
        if info is None:
            return False
        if info.status == RequestStatus.PREFILLING:
            self._preempt_lane()
            return True
        if info.status != RequestStatus.ACTIVE:
            return False
        self._release_slot(self._slot_of(rid))
        info.status = RequestStatus.PREEMPTED
        info.preemptions += 1
        self.stats["preempted"] += 1
        self._enqueue(info)
        return True

    def _expire_deadlines(self) -> None:
        """FAIL every request whose deadline has passed, waiting or active,
        through the same eviction path as cancellation."""
        now = self._step_no
        for rid in [
            r
            for r in self._waiting
            if self._reqs[r].deadline is not None and now > self._reqs[r].deadline
        ]:
            self._waiting.remove(rid)
            self.stats["expired"] += 1
            self._finish(
                self._reqs[rid], RequestStatus.FAILED, "deadline expired in queue"
            )
        if self._lane is not None:
            info = self._reqs[self._lane.rid]
            if info.deadline is not None and now > info.deadline:
                self._drop_lane()
                self.stats["expired"] += 1
                self._finish(
                    info, RequestStatus.FAILED, "deadline expired while prefilling"
                )
        for slot in [
            s
            for s, st in sorted(self._slots.items())
            if self._reqs[st.rid].deadline is not None
            and now > self._reqs[st.rid].deadline
        ]:
            info = self._reqs[self._slots[slot].rid]
            self._release_slot(slot)
            self.stats["expired"] += 1
            self._finish(info, RequestStatus.FAILED, "deadline expired while active")

    def _blocks_needed(self, info: _ReqInfo) -> int:
        """Free blocks the paged admission of ``info`` would consume right
        now (worst-case reservation minus prefix aliases, plus a CoW
        target) — the same arithmetic `_admit_waiting_paged` commits."""
        bs = self.scfg.block_size
        total = -(-(len(info.prompt) + info.budget) // bs)
        if not self.scfg.prefix_sharing:
            return total
        shared_full, shared_tail = self.pool.match_prefix(info.prompt.tolist())
        n_shared = len(shared_full) + (1 if shared_tail is not None else 0)
        cow = shared_tail is not None and info.budget > 1
        return total - n_shared + (1 if cow else 0)

    def _preempt_pass(self) -> None:
        """Priority preemption: while the best waiting request is starved
        (no free slot, or — paged — not enough free blocks) and a strictly
        lower-priority request is active, evict the worst victim (lowest
        priority, then youngest) and retry.  Victims recover bitwise after
        re-admission, so a preemption that frees less than hoped (shared
        blocks stay referenced) costs replay latency, never correctness."""
        while self._waiting:
            head = self._reqs[self._waiting[0]]
            starved = not self._free or (
                self._paged and self._blocks_needed(head) > self.pool.free_blocks
            )
            if not starved:
                return
            victims = sorted(
                (self._reqs[st.rid].priority, -self._reqs[st.rid].seq, st.rid)
                for st in self._slots.values()
                if self._reqs[st.rid].priority < head.priority
            )
            if not victims:
                return
            self.preempt(victims[0][2])

    # ---------------------------------------------------------- integrity --
    def _quarantine(self, slot: int, reason: str) -> None:
        """Corruption response: FAIL the request in ``slot`` and release
        its resources through the ordinary eviction path — pool invariants
        hold and the other rows never notice (slot rows are
        computationally independent)."""
        info = self._reqs[self._slots[slot].rid]
        self._release_slot(slot)
        self.stats["quarantined"] += 1
        self._finish(info, RequestStatus.FAILED, reason)

    def _audit_kv_checksums(self) -> None:
        """kv_checksum mode: recompute per-physical-block sums and compare
        against last step's mirror.  A block that changed without a legal
        write this step (``self._touched``) is corrupt: every request
        referencing it is quarantined.  NaN sums compare equal to
        themselves here, so an already-quarantined poisoned block does not
        re-fire once it sits idle in the free list."""
        sums = np.asarray(self._pool_sums(self.caches))
        prev = self._kv_sums
        changed = (sums != prev) & ~(np.isnan(sums) & np.isnan(prev))
        if self._touched:
            changed[list(self._touched)] = False
        prefix = "sdc: " if self._abft else ""
        for b in np.nonzero(changed)[0]:
            b = int(b)
            owners = [
                s
                for s, row in self._rows.items()
                if b in row.blocks or row.cow_dst == b
            ]
            for s in owners:
                if s in self._slots:
                    self._quarantine(
                        s,
                        f"{prefix}KV corruption: block {b} checksum "
                        f"changed without a write",
                    )
        self._kv_sums = sums

    def arm_fault(
        self,
        site: int,
        call_idx: int,
        row: int,
        col: int,
        bit: int,
        layer: int = -1,
    ) -> None:
        """Arm the one-shot SDC injection operand for the next decode step
        (seeded chaos harness; see kernels/abft.py for the site codes, the
        ``col == -1`` largest-magnitude targeting, and the ``layer``
        semantics — ``-1`` targets checks outside the layer scan, e.g. the
        unembed GEMM).  The operand is cleared after the faulty pass, so
        the detect->retry re-execution models a *transient* flip and runs
        clean."""
        if not self._abft:
            raise ValueError(
                "arm_fault needs the abft pipeline: set "
                "KernelConfig.abft='checksum' (or 'paranoid')"
            )
        self._fault = np.array(
            [site, call_idx, row, col, bit, layer, 0, 0], np.int32
        )

    def _sdc_recover(self, flags: int, toks, keys, ts):
        """Detect -> localize -> retry.  Roll the donated caches back one
        position and re-execute the step on the oracle substrate with the
        fault operand disarmed: KV writes are positionally idempotent (the
        write position depends on lengths and tables, never on values), so
        the retry overwrites whatever KV the faulty pass poisoned.  A
        retry that still fails its checksums — or any weight-fingerprint
        mismatch — is unlocalizable: raise BEFORE emission, so the journal
        never records a poisoned token and the newest snapshot restores a
        corruption-free state."""
        self.stats["sdc_detected"] += 1
        if flags & 2:
            raise SDCUnlocalizedError(
                "weight fingerprint mismatch: parameter corruption cannot "
                "be retried away; restore from the newest snapshot with "
                "freshly loaded params"
            )
        # a step-level checksum cannot name the victim row, so every live
        # request is charged one retry; repeat offenders are quarantined
        # as the probable corruption source before the re-execution
        for s in sorted(self._slots):
            if self._slots[s].sdc_retries >= SDC_RETRY_BUDGET:
                self._quarantine(s, "sdc: retry budget exhausted")
            else:
                self._slots[s].sdc_retries += 1
        if self._rewind is None:
            self._rewind = jax.jit(
                lambda c: {**c, "len": c["len"] - 1}, donate_argnums=(0,)
            )
        if self._retry_fn is None:
            self._retry_fn = (
                self._decode if self._attn is None else self._make_decode(None)
            )
        self.caches = self._rewind(self.caches)
        self.stats["sdc_retried"] += 1
        # disarmed fault, but with the scrub flag set: the retry is the
        # one step that must rule out weight corruption regardless of the
        # scrub cadence before its checksum verdict is trusted
        from repro.kernels.abft import FAULT_SCRUB

        retry_fault = np.zeros((8,), np.int32)
        retry_fault[FAULT_SCRUB] = 1
        (nxt, bad, flags2), self.caches = self._retry_fn(
            self.params, toks, self.caches, keys, ts,
            jnp.asarray(retry_fault),
        )
        if int(flags2):
            raise SDCUnlocalizedError(
                "checksum failure persisted across the oracle-substrate "
                "retry: corruption is unlocalizable; restore from the "
                "newest snapshot"
            )
        return nxt, bad

    # -------------------------------------------------------------- drive --
    def step(self, on_token: TokenCallback | None = None) -> bool:
        """One engine iteration: expire deadlines, preempt for starved
        higher-priority arrivals, backfill free slots from the queue, then
        advance every occupied slot by one decode token.  Returns False
        once the engine is idle.  When a RecoveryManager is attached, the
        step's emitted-token deltas are journaled (and a snapshot staged on
        cadence) before control returns — the crash-durability boundary is
        the end of every step."""
        alive = self._step_core(on_token)
        if self.recovery is not None:
            self.recovery.after_step()
        return alive

    def _step_core(self, on_token: TokenCallback | None) -> bool:
        if self._abft and self._kv_sums is not None:
            # audit BEFORE decode, against the blocks the PREVIOUS step
            # legally wrote: an inter-step KV flip quarantines its owner
            # before the poisoned attention read, so the victim's partial
            # output stays a clean oracle prefix and survivors never see
            # the corrupt block
            self._audit_kv_checksums()
        self._step_no += 1
        self._touched = {kvcache.SINK_BLOCK}
        self._expire_deadlines()
        self._preempt_pass()
        admitted = False
        if self._chunk:
            admitted = self._schedule_chunks(on_token)
        else:
            while self._free and self._waiting:
                if not self._admit_waiting(on_token):
                    break  # paged: head of queue waits for free blocks
                admitted = True
        if self._paged:
            self._resolve_cow()
        if not self._slots:
            if self._lane is not None:
                # a mid-flight prefill lane IS progress: decode has nothing
                # to do yet, but the engine is anything but idle
                self._stalled = 0
                return True
            if not self._waiting:
                self._stalled = 0
                return False
            if admitted:
                # budget-1 admissions finished instantly: that is progress
                self._stalled = 0
            else:
                # zero active slots, zero admissions, a non-empty queue:
                # nothing inside the engine can free capacity.  Shed the
                # head after `stall_patience` such steps instead of
                # spinning forever on externally-held or leaked blocks.
                self._stalled += 1
                if self._stalled >= self.scfg.stall_patience:
                    info = self._reqs[self._waiting.pop(0)]
                    self.stats["shed"] += 1
                    self._finish(
                        info,
                        RequestStatus.REJECTED,
                        f"shed by watchdog: no admission progress in "
                        f"{self._stalled} idle steps",
                    )
                    self._stalled = 0
            return bool(self._waiting)
        self._stalled = 0

        B = self.scfg.batch
        keys = np.zeros((B, 2), np.uint32)
        ts = np.zeros((B,), np.int32)
        for s, st in self._slots.items():
            keys[s], ts[s] = self._reqs[st.rid].key, st.emitted
        if self._kv_sums is not None:
            # the one block each live row legally appends to this step:
            # decode writes KV at position plen + emitted - 1 (the first
            # generated token's KV lands on the next step's feed)
            bs = self.scfg.block_size
            for s, st in self._slots.items():
                row = self._rows[s]
                self._touched.add(row.blocks[(row.plen + st.emitted - 1) // bs])
        toks = jnp.asarray(self._cur_tok[:, None])
        jkeys, jts = jnp.asarray(keys), jnp.asarray(ts)
        if self._abft:
            fault = self._fault.copy()
            from repro.kernels.abft import FAULT_SCRUB

            fault[FAULT_SCRUB] = self._step_no % self.scfg.kernel.scrub_every == 0
            (nxt, bad, flags), self.caches = self._decode_call(
                self.params, toks, self.caches, jkeys, jts,
                jnp.asarray(fault),
            )
            self._fault = np.zeros((8,), np.int32)  # transient: one shot
            if int(flags):
                nxt, bad = self._sdc_recover(int(flags), toks, jkeys, jts)
        else:
            (nxt, bad), self.caches = self._decode_call(
                self.params, toks, self.caches, jkeys, jts
            )
        nxt = np.asarray(nxt)
        bad = np.asarray(bad)
        self._cur_tok = nxt.copy()
        if self.scfg.guard_nan and bad.any():
            # quarantine BEFORE emission: a poisoned row's sampled token is
            # garbage and must reach neither the output nor the journal
            for s in [s for s in sorted(self._slots) if bad[s]]:
                self._quarantine(
                    s, "non-finite logits: KV/activation corruption"
                )

        finished = []
        for s in sorted(self._slots):
            st = self._slots.get(s)
            if st is None:
                continue  # an on_token callback cancelled this row mid-loop
            tok = int(nxt[s])
            out = self._outputs[st.rid]
            if st.emitted < st.replay:
                # preemption recovery: the decode programs are
                # deterministic, so the replayed token must re-derive the
                # recorded one bitwise; it was already emitted pre-eviction
                assert tok == out[st.emitted], (
                    f"request {st.rid}: recovery replay diverged at token "
                    f"{st.emitted} ({tok} != recorded {out[st.emitted]})"
                )
                st.emitted += 1
                if st.emitted >= st.budget:
                    # crash recovery can replay a request to COMPLETION
                    # (it finished after the last snapshot): the journaled
                    # final token re-derives here and no fresh emission
                    # remains to trigger the ordinary finish path below
                    finished.append((s, st.rid))
                continue
            out.append(tok)
            st.emitted += 1
            done = st.emitted >= st.budget
            self._emit_cbs(self._reqs[st.rid], tok, st.emitted - 1, done, on_token)
            if done:
                finished.append((s, st.rid))
        for s, rid in finished:
            st = self._slots.get(s)
            if st is None or st.rid != rid:
                continue  # the done-callback already cancelled it
            self._release_slot(s)  # backfilled at the next step
            self._finish(self._reqs[rid], RequestStatus.FINISHED, "")
        if self._kv_sums is not None and not self._abft:
            self._audit_kv_checksums()
        return True

    def pop_result(self, rid: int) -> RequestResult:
        """Take a request's :class:`RequestResult`.  Terminal requests are
        consumed (their id becomes reusable); a live request's result is a
        non-consuming snapshot of its current status and partial tokens;
        an unknown id reports ``UNKNOWN`` instead of raising.  Long-running
        step()-driven servers must pop terminal results, or completed
        outputs accumulate without bound."""
        info = self._reqs.get(rid)
        if info is None:
            return RequestResult(
                RequestStatus.UNKNOWN,
                np.zeros((0,), np.int32),
                reason="request id never submitted (or already popped)",
            )
        tokens = np.asarray(self._outputs[rid], np.int32)
        result = RequestResult(
            info.status, tokens, info.reason, info.preemptions, info.ttft
        )
        if info.status in TERMINAL_STATUSES:
            del self._reqs[rid]
            del self._outputs[rid]
            if self.recovery is not None:
                self.recovery.record_pop(rid)
        return result

    def run(
        self,
        requests: list[Request] = (),
        on_token: TokenCallback | None = None,
    ) -> list[RequestResult]:
        """Submit ``requests``, drive the engine dry, and return each
        request's :class:`RequestResult` (in submission order; array-like,
        so legacy token-array callers keep working).  Returned results are
        evicted from the engine (their ids become reusable)."""
        rids = [self.submit(r) for r in requests]
        while self.step(on_token):
            pass
        return [self.pop_result(r) for r in rids]

    # legacy API (PR-2-era callers): identical signature, continuous core
    def generate(self, requests: list[Request]) -> list[RequestResult]:
        return self.run(requests)

    def close(self) -> None:
        """Flush and close the recovery journal (no-op without durability,
        idempotent).  Simulated crashes skip this on purpose — every
        journal record is already fsync'd at the step boundary that
        produced it."""
        if self.recovery is not None:
            self.recovery.close()
            self.recovery = None

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class StaticEngine:
    """The pre-continuous static-batch engine, kept as the measured
    baseline: requests are packed into fixed batches, left-padded to the
    longest prompt, and decoded in lockstep to the largest
    ``max_new_tokens`` in the batch.  It shares the continuous engine's
    decode-attention substrate and donated caches, so the serve bench A/B
    measures scheduling, not kernels."""

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        if scfg.kv_layout != "contiguous":
            # silently serving contiguous numbers under a paged config
            # would corrupt every A/B built on this baseline
            raise ValueError(
                "StaticEngine serves the contiguous layout only (fixed "
                "lockstep batches have no block pool); use Engine for "
                "kv_layout='paged', or drop kv_layout/num_blocks from "
                "ServeConfig for the static baseline"
            )
        self.cfg = cfg
        self.model = build(cfg)
        self.params = params
        self.scfg = scfg
        model = self.model
        impl = _pallas_mm if scfg.matmul == "pallas" else None
        attn = "flash" if scfg.attention == "flash" else None

        def prefill_fn(params, toks, caches):
            with L.matmul_override(impl):
                return model.prefill(params, toks, caches)

        def decode_fn(params, toks, caches):
            with L.matmul_override(impl), L.attention_override(attn):
                return model.decode_step(params, toks, caches)

        self._prefill = jax.jit(prefill_fn)
        # same matmul/attention substrates + donated caches as the
        # continuous engine, so the bench A/B isolates scheduling
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))

    def _sample(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / self.scfg.temperature, axis=-1
        ).astype(jnp.int32)

    def _generate_batch(
        self,
        requests: list[Request],
        rids: list[int],
        on_token: TokenCallback | None,
    ) -> list[np.ndarray]:
        scfg = self.scfg
        plen = max(len(r.prompt) for r in requests)
        prompts = np.zeros((scfg.batch, plen), np.int32)
        for i, r in enumerate(requests):
            prompts[i, plen - len(r.prompt) :] = r.prompt  # left-pad
        max_new = max(r.max_new_tokens for r in requests)

        caches = self.model.init_caches(scfg.batch, scfg.max_len)
        logits, caches = self._prefill(self.params, jnp.asarray(prompts), caches)
        key = jax.random.PRNGKey(scfg.seed)
        outs = []
        tok = self._sample(logits, key)
        outs.append(np.asarray(tok))
        self._emit(requests, rids, outs, on_token)
        for _ in range(max_new - 1):
            key, sub = jax.random.split(key)
            logits, caches = self._decode(self.params, tok[:, None], caches)
            tok = self._sample(logits, sub)
            outs.append(np.asarray(tok))
            self._emit(requests, rids, outs, on_token)
        gen = np.stack(outs, axis=1)  # (B, max_new)
        return [gen[i, : r.max_new_tokens] for i, r in enumerate(requests)]

    @staticmethod
    def _emit(requests, rids, outs, on_token):
        if on_token is None:
            return
        t = len(outs) - 1
        for i, r in enumerate(requests):
            if t < r.max_new_tokens:
                on_token(rids[i], int(outs[-1][i]), t, t == r.max_new_tokens - 1)

    def generate(
        self,
        requests: list[Request],
        on_token: TokenCallback | None = None,
    ) -> list[np.ndarray]:
        """Serve in fixed batches of ``scfg.batch`` requests."""
        results: list[np.ndarray] = []
        B = self.scfg.batch
        for lo in range(0, len(requests), B):
            chunk = requests[lo : lo + B]
            rids = [
                r.request_id if r.request_id is not None else lo + i
                for i, r in enumerate(chunk)
            ]
            results.extend(self._generate_batch(chunk, rids, on_token))
        return results
