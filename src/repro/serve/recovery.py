"""Crash-consistent serving: engine snapshots + a write-ahead journal.

A process crash (OOM kill, preemptible-VM reclaim, kernel panic) used to
lose everything PR 5/6 made precious: the paged block pools, the radix
prefix index, and every in-flight request.  This module makes that state
durable by composing the repo's two existing hard primitives:

  * the **atomic async checkpoint idiom** (`ckpt/checkpoint.py`): stage to
    host RAM synchronously at a step boundary, write npz + manifest on a
    background thread into a tmp dir, publish with one `os.rename` — a
    crash mid-write never corrupts the newest published snapshot;
  * the **bitwise teacher-forced replay path** (PR 6 preemption recovery):
    decode is deterministic and sampling folds ``(seed, rid, t)``, so
    recorded tokens re-derive bitwise through the same compiled programs
    regardless of scheduling drift after restart.

Durability contract
-------------------

``EngineSnapshot`` (on disk: ``snap_<gen>_<step>/state.npz +
manifest.json``) captures the FULL serving state at a step boundary: every
per-layer KV pool / block table / length tensor, ``_cur_tok``, the waiting
queue, per-request ``_ReqInfo`` (prompt, budget, priority, absolute
deadline, arrival seq, status, recorded tokens), slot states incl. replay
counters, paged row ownership, and the whole :class:`BlockPool` —
refcounts, free list, external holds, and the radix prefix index, so
restored admissions keep aliasing restored physical blocks.  The npz's
sha256 lives in the manifest; a snapshot that fails verification is
quarantined (renamed ``*.corrupt``) and recovery falls back to the next
older one, or to a cold journal-only replay.

The **write-ahead journal** (``wal_<gen>_<step>.jsonl``, one crc32-guarded
JSON record per line, fsync'd once per engine step and at every
submit/cancel/pop boundary) records what happened *between* snapshots:
submits (the reconstructed ``_ReqInfo`` fields — absolute deadline, not
the relative ``deadline_steps``), cancels, result pops, and per-step
emitted-token deltas.  The journal rotates at each snapshot, so

    recovery = newest valid snapshot
             + every journal segment at-or-after it, in (gen, step) order.

Restored requests that were ACTIVE at the snapshot resume decoding from
the restored KV; requests admitted after it re-prefill their prompts
through the restored prefix index; in both cases journaled tokens are
teacher-force replayed with the PR-6 per-step equality asserts — survivor
outputs are **bitwise identical** to the never-crashed run.  A torn final
journal line (crash mid-write) is detected by its crc and dropped, along
with anything after it.

What is NOT durable: tokens generated after the last fsync'd journal
record (at most one step), external ``BlockPool.reserve`` holds (the
holder was a co-tenant of the dead process, so restore releases them back
to the free list), and ``on_token`` callback delivery (replayed tokens
are not re-streamed, matching preemption-recovery semantics).

Generations: every restart increments ``gen`` (max on disk + 1), so a
restored engine's snapshot/segment names never collide with its ancestors'
and sort strictly after them; the anchor snapshot taken at restore folds
the replayed tail into the new generation, which is what makes *chained*
crashes (crash during or after recovery) recover correctly.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import threading
import zlib
from collections import deque
from typing import Any

import jax
import numpy as np

from repro.ckpt.checkpoint import _from_savable, _to_savable
from repro.serve.engine import (
    TERMINAL_STATUSES,
    Engine,
    RequestStatus,
    ServeConfig,
    _ReqInfo,
    _SlotState,
)

_FORMAT = 1


class CorruptSnapshot(Exception):
    """A published snapshot failed integrity verification."""


# ------------------------------------------------------------- disk names --
def _snap_name(gen: int, step: int) -> str:
    return f"snap_{gen:04d}_{step:08d}"


def _wal_name(gen: int, step: int) -> str:
    return f"wal_{gen:04d}_{step:08d}.jsonl"


def _parse_key(name: str, prefix: str) -> tuple[int, int] | None:
    """(gen, step) from a snapshot/segment name; None for foreign files
    (tmp dirs, quarantined snapshots, strays)."""
    stem = name[len(prefix) :].removesuffix(".jsonl")
    parts = stem.split("_")
    if len(parts) != 2:
        return None
    try:
        return int(parts[0]), int(parts[1])
    except ValueError:
        return None


def _snapshot_keys(directory: str) -> list[tuple[int, int]]:
    out = []
    for name in os.listdir(directory):
        if name.startswith("snap_") and not name.endswith((".tmp", ".corrupt")):
            key = _parse_key(name, "snap_")
            if key is not None and os.path.isdir(os.path.join(directory, name)):
                out.append(key)
    return sorted(out)


def _segment_keys(directory: str) -> list[tuple[int, int]]:
    out = []
    for name in os.listdir(directory):
        if name.startswith("wal_") and name.endswith(".jsonl"):
            key = _parse_key(name, "wal_")
            if key is not None:
                out.append(key)
    return sorted(out)


def _disk_generations(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    return [g for g, _ in _snapshot_keys(directory) + _segment_keys(directory)]


# ---------------------------------------------------------------- journal --
class Journal:
    """Append-only crc32-per-line JSON log.  ``append`` buffers; ``commit``
    flushes and (subject to ``fsync_every``) fsyncs — the engine commits
    once per step, and forces a sync at submit/cancel/pop boundaries so
    client-visible events are never lost to a crash."""

    def __init__(self, path: str, fsync_every: int = 1):
        self.path = path
        self._f = open(path, "ab")
        self._fsync_every = max(1, int(fsync_every))
        self._commits_since_sync = 0
        self._dirty = False

    def append(self, rec: dict) -> None:
        body = json.dumps(rec, separators=(",", ":")).encode()
        self._f.write(b"%08x %s\n" % (zlib.crc32(body), body))
        self._dirty = True

    def commit(self, force: bool = False) -> None:
        if not self._dirty and not force:
            return
        self._f.flush()
        self._commits_since_sync += 1
        if force or self._commits_since_sync >= self._fsync_every:
            os.fsync(self._f.fileno())
            self._commits_since_sync = 0
        self._dirty = False

    def close(self) -> None:
        self.commit(force=True)
        self._f.close()


def read_journal(path: str) -> tuple[list[dict], int]:
    """Parse one segment; returns (records, torn_lines).  Reading stops at
    the first line whose crc or JSON fails — a crash mid-append tears only
    the final line, and nothing after a torn line is trustworthy."""
    recs: list[dict] = []
    torn = 0
    with open(path, "rb") as f:
        raw = f.read()
    for line in raw.split(b"\n"):
        if not line:
            continue
        try:
            crc, body = line.split(b" ", 1)
            if int(crc, 16) != zlib.crc32(body):
                raise ValueError("crc mismatch")
            recs.append(json.loads(body))
        except Exception:
            torn += 1
            break
    return recs, torn


def _submit_record(info: _ReqInfo) -> dict:
    # absolute deadline + effective budget + original seq: replay rebuilds
    # _ReqInfo directly instead of re-running submit()'s validation against
    # a drifted _step_no
    return {
        "t": "submit",
        "rid": info.rid,
        "prompt": [int(t) for t in info.prompt],
        "budget": info.budget,
        "priority": info.priority,
        "deadline": info.deadline,
        "seq": info.seq,
        "status": info.status.value,
        "reason": info.reason,
        "seed": info.seed,
        "submitted": info.submitted,
        "ttft": info.ttft,
    }


# ----------------------------------------------------------- snapshotting --
def _scfg_fingerprint(scfg: ServeConfig) -> dict:
    """The config fields a snapshot's device shapes and bitwise token
    stream depend on; restore refuses a mismatch loudly."""
    return {
        "batch": scfg.batch,
        "max_len": scfg.max_len,
        "temperature": scfg.temperature,
        "seed": scfg.seed,
        "prefill_bucket": scfg.prefill_bucket,
        "matmul": scfg.matmul,
        "attention": scfg.attention,
        "kv_layout": scfg.kv_layout,
        "block_size": scfg.block_size,
        "num_blocks": (
            scfg.resolved_num_blocks() if scfg.kv_layout == "paged" else None
        ),
        "prefix_sharing": scfg.prefix_sharing,
        "decode_block": scfg.decode_block,
    }


def _host_state(eng: Engine) -> dict:
    """Deep-copied, JSON-safe host bookkeeping — the background writer must
    see a frozen image while the engine keeps stepping.

    A mid-flight chunked-prefill lane is serialized as its request
    REQUEUED (WAITING, slot freed, its reservation released in the
    persisted pool image by ``KVLayout.to_state``): the lane has published
    nothing — zero tokens, no device block table or slot writes — so
    restore is a plain re-prefill, bitwise identical by determinism."""
    free = list(eng._free)
    waiting = list(eng._waiting)
    requeued: set[int] = set()
    lane = eng._lane
    if lane is not None:
        free.append(lane.slot)
        waiting = sorted(
            waiting + [lane.rid],
            key=lambda r: (-eng._reqs[r].priority, eng._reqs[r].seq),
        )
        requeued.add(lane.rid)
    reqs = []
    for info in eng._reqs.values():
        rec = _submit_record(info)
        if info.rid in requeued:
            rec["status"] = RequestStatus.WAITING.value
        reqs.append(rec)
    return {
        "step_no": eng._step_no,
        "next_rid": eng._next_rid,
        "next_seq": eng._next_seq,
        "stalled": eng._stalled,
        "stats": dict(eng.stats),
        "free": free,
        "waiting": waiting,
        "reqs": reqs,
        "outputs": {str(rid): list(out) for rid, out in eng._outputs.items()},
        "slots": {
            str(s): {
                "rid": st.rid,
                "emitted": st.emitted,
                "budget": st.budget,
                "replay": st.replay,
            }
            for s, st in eng._slots.items()
        },
        **eng.kv.to_state(),
    }


def _stage(eng: Engine) -> dict:
    """Synchronous device->host snapshot at a step boundary.  ``np.array``
    (not ``asarray``) forces a copy: the cache buffers are donated through
    the next decode step and may be rewritten in place while the
    background thread is still serializing."""
    leaves = jax.tree_util.tree_leaves(eng.caches)
    arrays = {
        f"cache_{i:04d}": np.array(jax.device_get(leaf))
        for i, leaf in enumerate(leaves)
    }
    arrays["cur_tok"] = eng._cur_tok.copy()
    meta = {
        "format": _FORMAT,
        "step": eng._step_no,
        "n_cache_leaves": len(leaves),
        "scfg": _scfg_fingerprint(eng.scfg),
        "host": _host_state(eng),
        "leaves": {
            k: [list(v.shape), str(v.dtype)] for k, v in arrays.items()
        },
    }
    return {"arrays": arrays, "meta": meta}


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_snapshot(directory: str, name: str, staged: dict, keep: int) -> str:
    """Background-thread body: npz + sha256'd manifest into a tmp dir,
    fsync everything, one rename to publish, then GC."""
    tmp = os.path.join(directory, name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    npz = os.path.join(tmp, "state.npz")
    np.savez(npz, **{k: _to_savable(v) for k, v in staged["arrays"].items()})
    with open(npz, "rb") as f:
        sha = hashlib.sha256(f.read()).hexdigest()
        os.fsync(f.fileno())
    manifest = dict(staged["meta"], sha256=sha)
    mpath = os.path.join(tmp, "manifest.json")
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    final = os.path.join(directory, name)
    shutil.rmtree(final, ignore_errors=True)
    os.rename(tmp, final)
    _fsync_dir(directory)
    _gc(directory, keep)
    return final


def _gc(directory: str, keep: int) -> None:
    """Drop all but the newest ``keep`` snapshots, and every journal
    segment older than the oldest kept snapshot (segments at-or-after it
    are still needed for replay)."""
    snaps = _snapshot_keys(directory)
    if len(snaps) <= keep:
        return
    kept_floor = snaps[-keep]
    for key in snaps[:-keep]:
        shutil.rmtree(
            os.path.join(directory, _snap_name(*key)), ignore_errors=True
        )
    for key in _segment_keys(directory):
        if key < kept_floor:
            try:
                os.remove(os.path.join(directory, _wal_name(*key)))
            except OSError:
                pass


def _load_snapshot(directory: str, key: tuple[int, int]) -> dict:
    """Read + verify one published snapshot; raises CorruptSnapshot on any
    integrity failure (missing file, bad sha, unreadable npz)."""
    path = os.path.join(directory, _snap_name(*key))
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        npz = os.path.join(path, "state.npz")
        with open(npz, "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
        if sha != manifest.get("sha256"):
            raise CorruptSnapshot(
                f"{path}: state.npz sha256 {sha[:12]}… != manifest "
                f"{str(manifest.get('sha256'))[:12]}…"
            )
        with np.load(npz) as data:
            arrays = {
                k: _from_savable(data[k], manifest["leaves"][k][1])
                for k in data.files
            }
    except CorruptSnapshot:
        raise
    except Exception as e:
        raise CorruptSnapshot(f"{path}: unreadable snapshot ({e})") from e
    return {"arrays": arrays, "meta": manifest}


def _quarantine(directory: str, key: tuple[int, int]) -> str:
    """Rename a corrupt snapshot out of the recovery search path (kept on
    disk for forensics, never deleted by GC)."""
    src = os.path.join(directory, _snap_name(*key))
    dst = src + ".corrupt"
    n = 0
    while os.path.exists(dst):
        n += 1
        dst = f"{src}.corrupt{n}"
    os.rename(src, dst)
    return os.path.basename(dst)


# --------------------------------------------------------------- manager --
class RecoveryManager:
    """Engine-side durability driver: journals lifecycle events as they
    happen, commits the journal once per step, and stages + publishes a
    snapshot every ``every`` steps (staging is synchronous at the step
    boundary; serialization and the atomic publish run on a background
    thread).  Create via :meth:`attach`."""

    def __init__(
        self,
        eng: Engine,
        directory: str,
        every: int = 32,
        keep: int = 3,
        fsync_every: int = 1,
    ):
        os.makedirs(directory, exist_ok=True)
        self.eng = eng
        self.directory = directory
        self.every = max(1, int(every))
        self.keep = max(1, int(keep))
        self.fsync_every = max(1, int(fsync_every))
        self.gen = max(_disk_generations(directory), default=-1) + 1
        self._thread: threading.Thread | None = None
        # journaled token counts per rid: after_step appends only deltas
        self._logged = {rid: len(out) for rid, out in eng._outputs.items()}
        self._last_snap_step = eng._step_no
        self.journal = Journal(
            os.path.join(directory, _wal_name(self.gen, eng._step_no)),
            fsync_every=self.fsync_every,
        )

    @classmethod
    def attach(
        cls,
        eng: Engine,
        directory: str,
        every: int = 32,
        keep: int = 3,
        fsync_every: int = 1,
    ) -> "RecoveryManager":
        mgr = cls(eng, directory, every=every, keep=keep, fsync_every=fsync_every)
        eng.recovery = mgr
        if eng._step_no > 0 or eng._reqs:
            # restored (or mid-flight) engine: anchor the new generation
            # with an immediate snapshot so its journal segments replay
            # from a self-contained base even after older-gen GC
            mgr.snapshot()
        return mgr

    # ------------------------------------------------------------ hooks --
    def record_submit(self, info: _ReqInfo) -> None:
        self.journal.append(_submit_record(info))
        self._logged[info.rid] = len(self.eng._outputs[info.rid])
        self.journal.commit(force=True)  # durable before the submit acks

    def record_cancel(self, rid: int, reason: str) -> None:
        self.journal.append({"t": "cancel", "rid": rid, "reason": reason})
        self.journal.commit(force=True)

    def record_pop(self, rid: int) -> None:
        self.journal.append({"t": "pop", "rid": rid})
        self._logged.pop(rid, None)
        self.journal.commit(force=True)

    def after_step(self) -> None:
        """End-of-step hook: journal this step's emitted-token deltas,
        commit, and snapshot on cadence."""
        eng = self.eng
        for rid, out in eng._outputs.items():
            have = self._logged.get(rid, 0)
            if len(out) > have:
                self.journal.append(
                    {"t": "tok", "rid": rid, "toks": [int(t) for t in out[have:]]}
                )
                self._logged[rid] = len(out)
        self.journal.commit()
        if eng._step_no - self._last_snap_step >= self.every:
            self.snapshot()

    # --------------------------------------------------------- snapshot --
    def snapshot(self) -> None:
        """Stage now (synchronously, at a step boundary), publish in the
        background.  The journal rotates first, so the closed segment holds
        exactly the records up to this snapshot and the fresh one exactly
        those after it."""
        self.wait()
        eng = self.eng
        step = eng._step_no
        self.journal.close()
        self.journal = Journal(
            os.path.join(self.directory, _wal_name(self.gen, step)),
            fsync_every=self.fsync_every,
        )
        staged = _stage(eng)
        self._last_snap_step = step
        self._thread = threading.Thread(
            target=_write_snapshot,
            args=(self.directory, _snap_name(self.gen, step), staged, self.keep),
            daemon=True,
        )
        self._thread.start()
        eng.stats["snapshots"] += 1

    def wait(self) -> None:
        """Block until the in-flight snapshot write (if any) has published."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def close(self) -> None:
        self.wait()
        self.journal.close()


# ---------------------------------------------------------------- restore --
@dataclasses.dataclass
class RecoveryReport:
    """What a restore did — the launch CLI prints it, tests assert on it."""

    source: str                      # "snapshot" | "cold" | "fresh"
    snapshot_key: tuple | None       # (gen, step) restored from
    segments: int                    # journal segments replayed
    records: int                     # journal records applied
    torn_lines: int                  # crc-rejected (crash-torn) lines dropped
    resubmitted: int                 # requests rebuilt from submit records
    tokens_replayed: int             # journaled tokens appended past snapshot
    cancels: int
    pops: int
    quarantined: list[str]           # snapshots renamed *.corrupt this restore


def replay_lag(eng: Engine) -> int:
    """Tokens the engine still has to teacher-force re-derive before it has
    caught up with the journal: active-slot replay remainders plus recorded
    tokens of queued (not-yet-readmitted) requests.  Zero == fully caught
    up; `serve_bench` times recovery-to-readmit on this hitting zero."""
    lag = 0
    for st in eng._slots.values():
        lag += max(0, st.replay - st.emitted)
    for rid in eng._waiting:
        lag += len(eng._outputs.get(rid, ()))
    return lag


def _apply_snapshot(eng: Engine, snap: dict) -> None:
    meta = snap["meta"]
    want = _scfg_fingerprint(eng.scfg)
    got = meta["scfg"]
    diff = [k for k in want if want[k] != got.get(k)]
    if diff:
        raise ValueError(
            "snapshot was taken under an incompatible ServeConfig; "
            "differing fields: "
            + ", ".join(f"{k}: snapshot={got.get(k)!r} now={want[k]!r}"
                        for k in diff)
        )
    leaves, treedef = jax.tree_util.tree_flatten(eng.caches)
    n = meta["n_cache_leaves"]
    if n != len(leaves):
        raise ValueError(
            f"snapshot has {n} cache leaves, engine expects {len(leaves)}"
        )
    new_leaves = []
    for i, leaf in enumerate(leaves):
        arr = snap["arrays"][f"cache_{i:04d}"]
        if tuple(arr.shape) != tuple(leaf.shape) or str(arr.dtype) != str(
            leaf.dtype
        ):
            raise ValueError(
                f"snapshot cache leaf {i}: {arr.shape}/{arr.dtype} != "
                f"engine {tuple(leaf.shape)}/{leaf.dtype}"
            )
        new_leaves.append(jax.numpy.asarray(arr))
    eng.caches = jax.tree_util.tree_unflatten(treedef, new_leaves)
    eng._cur_tok = np.asarray(snap["arrays"]["cur_tok"], np.int32).copy()

    h = meta["host"]
    eng._step_no = int(h["step_no"])
    eng._next_rid = int(h["next_rid"])
    eng._next_seq = int(h["next_seq"])
    eng._stalled = int(h["stalled"])
    # in place: the KV layout counts into this same dict
    eng.stats.update({k: int(v) for k, v in h["stats"].items()})
    eng._free = deque(int(s) for s in h["free"])
    eng._waiting = [int(r) for r in h["waiting"]]
    eng._reqs = {}
    for r in h["reqs"]:
        rid = int(r["rid"])
        seed = int(r.get("seed", eng.scfg.seed))
        ttft = r.get("ttft")
        eng._reqs[rid] = _ReqInfo(
            rid=rid,
            prompt=np.asarray(r["prompt"], np.int32),
            budget=int(r["budget"]),
            priority=int(r["priority"]),
            deadline=None if r["deadline"] is None else int(r["deadline"]),
            seq=int(r["seq"]),
            status=RequestStatus(r["status"]),
            reason=r.get("reason", ""),
            seed=seed,
            key=eng._req_base_key(rid, seed),
            submitted=int(r.get("submitted", 0)),
            ttft=None if ttft is None else int(ttft),
        )
    eng._outputs = {
        int(rid): [int(t) for t in out] for rid, out in h["outputs"].items()
    }
    eng._slots = {
        int(s): _SlotState(
            rid=int(st["rid"]),
            emitted=int(st["emitted"]),
            budget=int(st["budget"]),
            replay=int(st["replay"]),
        )
        for s, st in h["slots"].items()
    }
    eng.kv.from_state(h, eng.caches)


def _apply_records(
    eng: Engine, recs: list[dict], report: RecoveryReport
) -> list[int]:
    """Replay journal records in order.  Token appends and cancels commute
    per rid (appends extend the recorded output whether or not the request
    is already terminal; a cancel freezes status but never the recorded
    tokens), so cross-generation segment concatenation stays consistent.
    Returns the rids whose results were popped pre-crash (applied last —
    the client already consumed them)."""
    pops: list[int] = []
    for rec in recs:
        t = rec["t"]
        rid = int(rec["rid"])
        report.records += 1
        if t == "submit":
            if rid in eng._reqs:
                continue  # defensive: already present via snapshot
            seed = int(rec.get("seed", eng.scfg.seed))
            ttft = rec.get("ttft")
            info = _ReqInfo(
                rid=rid,
                prompt=np.asarray(rec["prompt"], np.int32),
                budget=int(rec["budget"]),
                priority=int(rec["priority"]),
                deadline=(
                    None if rec["deadline"] is None else int(rec["deadline"])
                ),
                seq=int(rec["seq"]),
                status=RequestStatus(rec["status"]),
                reason=rec.get("reason", ""),
                seed=seed,
                key=eng._req_base_key(rid, seed),
                submitted=int(rec.get("submitted", 0)),
                ttft=None if ttft is None else int(ttft),
            )
            eng._reqs[rid] = info
            eng._outputs[rid] = []
            eng._next_rid = max(eng._next_rid, rid + 1)
            eng._next_seq = max(eng._next_seq, info.seq + 1)
            if info.status == RequestStatus.WAITING:
                eng._enqueue(info)
            report.resubmitted += 1
        elif t == "tok":
            if rid in eng._outputs:
                toks = [int(x) for x in rec["toks"]]
                eng._outputs[rid].extend(toks)
                report.tokens_replayed += len(toks)
        elif t == "cancel":
            info = eng._reqs.get(rid)
            if info is not None and info.status not in TERMINAL_STATUSES:
                eng.cancel(rid, rec.get("reason", "cancelled"))
            report.cancels += 1
        elif t == "pop":
            pops.append(rid)
            report.pops += 1
    return pops


def restore_engine(
    cfg: Any,
    params: Any,
    scfg: ServeConfig,
    directory: str | None = None,
) -> tuple[Engine, RecoveryReport]:
    """Rebuild a crashed engine from ``directory`` (default:
    ``scfg.snapshot_dir``): load the newest snapshot that verifies
    (quarantining corrupt ones), replay every journal segment at-or-after
    it, re-apply pre-crash cancels/pops, and arm the PR-6 replay counters
    so the next ``step()`` calls teacher-force journaled tokens with
    bitwise equality asserts.  ``scfg`` must match the crashed engine's
    config (shape/seed fingerprint is verified).  When
    ``scfg.snapshot_dir`` is set, a fresh-generation RecoveryManager is
    attached and an anchor snapshot taken, so chained crashes recover too.
    """
    directory = directory or scfg.snapshot_dir
    if not directory:
        raise ValueError("restore_engine needs a directory or scfg.snapshot_dir")
    eng = Engine(
        cfg,
        params,
        dataclasses.replace(
            scfg,
            durability=dataclasses.replace(scfg.durability, snapshot_dir=None),
        ),
    )
    report = RecoveryReport(
        source="fresh",
        snapshot_key=None,
        segments=0,
        records=0,
        torn_lines=0,
        resubmitted=0,
        tokens_replayed=0,
        cancels=0,
        pops=0,
        quarantined=[],
    )
    os.makedirs(directory, exist_ok=True)

    chosen: tuple[int, int] | None = None
    snap = None
    for key in reversed(_snapshot_keys(directory)):
        try:
            snap = _load_snapshot(directory, key)
        except CorruptSnapshot:
            report.quarantined.append(_quarantine(directory, key))
            continue
        chosen = key
        break
    if chosen is not None:
        _apply_snapshot(eng, snap)
        report.source = "snapshot"
        report.snapshot_key = chosen
        if eng.pool is not None and eng.pool.external:
            # external reserve holders died with the crashed process
            eng.pool.unreserve(sorted(eng.pool.external))

    segments = [
        k for k in _segment_keys(directory) if chosen is None or k >= chosen
    ]
    pops: list[int] = []
    for key in segments:
        recs, torn = read_journal(os.path.join(directory, _wal_name(*key)))
        report.segments += 1
        report.torn_lines += torn
        pops.extend(_apply_records(eng, recs, report))
    if chosen is None and report.records:
        report.source = "cold"

    for rid in pops:
        info = eng._reqs.get(rid)
        if info is None:
            continue
        if info.status not in TERMINAL_STATUSES:
            # the client consumed this result before the crash; finish the
            # zombie through the ordinary release path and evict it
            eng.cancel(rid, "result popped before crash")
        eng.pop_result(rid)

    # arm PR-6 teacher-forced replay: active slots re-derive journaled
    # tokens in place; queued requests with recorded tokens recover through
    # _activate's replay path on re-admission
    for st in eng._slots.values():
        st.replay = len(eng._outputs[st.rid])

    if scfg.snapshot_dir:
        RecoveryManager.attach(
            eng,
            directory,
            every=scfg.snapshot_every,
            keep=scfg.snapshot_keep,
            fsync_every=scfg.journal_fsync_every,
        )
        eng.scfg = scfg
    return eng, report
