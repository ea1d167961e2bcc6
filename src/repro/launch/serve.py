"""Production serving launcher on the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m-smoke

Generates a mixed-length synthetic workload, streams tokens through the
slot-based engine, and reports throughput plus per-token latency.  Pass
``--static`` to run the padded static-batch baseline instead (same workload,
same slot count) for an A/B on the spot.

Durability: ``--snapshot-dir DIR`` arms crash consistency (atomic engine
snapshots every ``--snapshot-every`` steps plus a write-ahead journal,
serve/recovery.py).  After a crash — try SIGKILL mid-run — relaunch with
``--resume`` and the same flags: the engine restores from the newest valid
snapshot, teacher-forces the journaled tokens back (bitwise identical to
the never-crashed run), and finishes the in-flight requests.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.arch.model_zoo import build
from repro.configs.registry import get
from repro.launch.cache import enable_compile_cache
from repro.serve.engine import (
    DurabilityConfig,
    Engine,
    KernelConfig,
    KVConfig,
    Request,
    SchedulerConfig,
    ServeConfig,
    StaticEngine,
)


def make_workload(
    cfg, n: int, max_new: int, seed: int = 0, deadline: int | None = None
) -> list[Request]:
    rng = np.random.default_rng(seed)
    return [
        Request(
            rng.integers(0, cfg.vocab, rng.integers(3, 16)).astype(np.int32),
            max_new=int(rng.integers(max(2, max_new // 4), max_new + 1)),
            request_id=i,
            deadline_steps=deadline,
        )
        for i in range(n)
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m-smoke")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-bucket", type=int, default=16)
    ap.add_argument("--matmul", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--attention", choices=("flash", "xla"), default="flash",
                    help="decode-attention substrate: ragged flash-decoding "
                         "or the masked dense/blockwise oracle")
    ap.add_argument("--abft", choices=("off", "checksum", "paranoid"),
                    default="off",
                    help="silent-data-corruption defense "
                         "(KernelConfig.abft): 'checksum' arms "
                         "checksum-carrying matmuls, a sampled attention "
                         "fingerprint, and a periodic weight scrub — "
                         "flagged steps are retried and, if the fault "
                         "persists, the offending request is quarantined; "
                         "'paranoid' re-verifies every step on the dense "
                         "oracle")
    ap.add_argument("--scrub-every", type=int, default=1,
                    help="abft: steps between full weight-fingerprint "
                         "scrubs (1 = every step; larger values amortize "
                         "the scrub read at the cost of up to N-1 steps "
                         "of weight-flip detection latency)")
    ap.add_argument("--kv-layout", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="KV cache layout (ServeConfig.kv_layout): "
                         "'contiguous' reserves slots x max_len positions "
                         "per layer; 'paged' carves the same HBM into "
                         "refcounted fixed-size blocks with per-request "
                         "block tables, so capacity tracks live tokens, "
                         "prompts sharing a prefix alias physical blocks "
                         "(copy-on-write), and --slots becomes a pure "
                         "scheduling cap.  Requires all-global attention; "
                         "the contiguous layout is the paged engine's "
                         "bitwise differential oracle")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged: tokens per physical KV block "
                         "(max-len must be a multiple)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged: pool blocks per layer incl. the sink "
                         "(default: the contiguous footprint, "
                         "slots*max_len/block_size + 1)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="paged: disable the radix prefix index "
                         "(every request gets private blocks)")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="bound the waiting queue: overflow submissions "
                         "end REJECTED immediately (load shedding); "
                         "default unbounded")
    ap.add_argument("--stall-patience", type=int, default=64,
                    help="consecutive no-progress idle steps before the "
                         "watchdog sheds the queue head instead of "
                         "livelocking")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="unified scheduler: split admission prefills into "
                         "fixed chunks of this many tokens and interleave "
                         "them with decode steps (0 = monolithic admission, "
                         "the bitwise oracle; max-len must be a multiple)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max prefill tokens advanced per engine step "
                         "(requires --prefill-chunk; default unlimited). "
                         "Lower budgets flatten decode ITL under admission "
                         "storms at the cost of TTFT")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request deadline in engine steps; expired "
                         "requests end FAILED with their partial output")
    ap.add_argument("--snapshot-dir", default=None,
                    help="arm crash consistency: atomic engine snapshots "
                         "plus a write-ahead journal under this directory "
                         "(created if missing); relaunch with --resume to "
                         "recover after a crash")
    ap.add_argument("--snapshot-every", type=int, default=32,
                    help="steps between snapshots (journal records land "
                         "every step regardless)")
    ap.add_argument("--resume", action="store_true",
                    help="restore from --snapshot-dir instead of submitting "
                         "a fresh workload: replay the journal, print the "
                         "recovery report, and finish the in-flight requests")
    ap.add_argument("--static", action="store_true",
                    help="run the padded static-batch baseline instead")
    ap.add_argument("--autotune", action="store_true",
                    help="plan the serving knobs with the DSE planner "
                         "(core/serveplan.py): sweep slots / kv layout / "
                         "block_size / num_blocks / prefill_chunk / "
                         "token_budget under an iso-HBM KV budget, take the "
                         "Pareto winner, and serve with it.  Overrides "
                         "--slots/--kv-layout/--block-size/--num-blocks/"
                         "--prefill-chunk/--token-budget; kernel and "
                         "durability flags still apply.  Winning plans "
                         "persist in REPRO_SERVE_PLAN_CACHE")
    ap.add_argument("--concurrency", type=int, default=None,
                    help="autotune: offered concurrency to plan for "
                         "(default: --requests)")
    args = ap.parse_args()
    if args.resume and not args.snapshot_dir:
        ap.error("--resume requires --snapshot-dir")
    if args.autotune and args.static:
        ap.error("--autotune plans the continuous engine (drop --static)")
    if args.static and (args.snapshot_dir or args.resume):
        ap.error("--snapshot-dir/--resume need the continuous engine "
                 "(drop --static)")
    if args.abft != "off" and args.kv_layout != "paged":
        ap.error("--abft localizes corruption through the paged pool's "
                 "per-block fingerprints (add --kv-layout paged)")

    enable_compile_cache()
    cfg = get(args.arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.autotune:
        from repro.core.serveplan import ServeWorkload

        scfg = ServeConfig.autotune(
            cfg,
            max_len=args.max_len,
            workload=ServeWorkload(
                concurrency=args.concurrency or args.requests,
                prompt_len=16,
                decode_len=max(2, args.new_tokens),
            ),
            temperature=args.temperature,
            seed=args.seed,
            kernel=KernelConfig(
                matmul=args.matmul, attention=args.attention,
                abft=args.abft, scrub_every=args.scrub_every,
            ),
            durability=DurabilityConfig(
                snapshot_dir=args.snapshot_dir,
                snapshot_every=args.snapshot_every,
            ),
        )
        plan = scfg.autotune_plan
        pred = plan.predicted
        print(
            f"[autotune] {plan.source}: slots={scfg.batch} "
            f"kv={scfg.kv_layout}/bs={scfg.kv.block_size}"
            f"/nb={scfg.kv.num_blocks} "
            f"chunk={scfg.prefill_chunk} budget={scfg.token_budget} "
            f"(predicted {pred.get('tokens_per_s', 0):.0f} tok/s over "
            f"{pred.get('swept_points', '?')} swept points, "
            f"frontier {plan.frontier_size})"
        )
    else:
        scfg = ServeConfig(
            max_len=args.max_len, temperature=args.temperature,
            seed=args.seed,
            scheduler=SchedulerConfig(
                batch=args.slots, prefill_bucket=args.prefill_bucket,
                prefill_chunk=args.prefill_chunk,
                token_budget=args.token_budget,
                max_waiting=args.max_waiting,
                stall_patience=args.stall_patience,
            ),
            kv=KVConfig(
                layout=args.kv_layout, block_size=args.block_size,
                num_blocks=args.num_blocks,
                prefix_sharing=not args.no_prefix_sharing,
            ),
            kernel=KernelConfig(
                matmul=args.matmul, attention=args.attention,
                abft=args.abft, scrub_every=args.scrub_every,
            ),
            durability=DurabilityConfig(
                snapshot_dir=args.snapshot_dir,
                snapshot_every=args.snapshot_every,
            ),
        )

    t0 = time.perf_counter()
    stamps: dict[int, list[float]] = {}

    def on_token(rid, tok, idx, done):
        stamps.setdefault(rid, []).append(time.perf_counter() - t0)

    if args.resume:
        from repro.serve import recovery

        eng, report = recovery.restore_engine(cfg, params, scfg)
        print(
            f"[resume] source={report.source} snapshot={report.snapshot_key} "
            f"segments={report.segments} records={report.records} "
            f"torn={report.torn_lines}"
        )
        print(
            f"[resume] resubmitted={report.resubmitted} "
            f"tokens_replayed={report.tokens_replayed} "
            f"cancels={report.cancels} pops={report.pops} "
            f"quarantined={report.quarantined or '[]'}"
        )
        n_reqs = len(eng._reqs)
        rids = sorted(eng._reqs)
        with eng:
            while eng.step(on_token):
                pass
            outs = [eng.pop_result(r) for r in rids]
    elif args.static:
        reqs = make_workload(
            cfg, args.requests, args.new_tokens, args.seed,
            deadline=args.deadline_steps,
        )
        n_reqs = len(reqs)
        outs = StaticEngine(cfg, params, scfg).generate(reqs, on_token=on_token)
    else:
        reqs = make_workload(
            cfg, args.requests, args.new_tokens, args.seed,
            deadline=args.deadline_steps,
        )
        n_reqs = len(reqs)
        with Engine(cfg, params, scfg) as eng:
            outs = eng.run(reqs, on_token=on_token)
    dt = time.perf_counter() - t0

    total_new = sum(len(o) for o in outs)
    deltas = [
        b - a
        for ts in stamps.values()
        for a, b in zip([0.0] + ts[:-1], ts)
    ]
    deltas.sort()
    p50 = deltas[len(deltas) // 2] if deltas else 0.0
    p95 = deltas[min(len(deltas) - 1, int(len(deltas) * 0.95))] if deltas else 0.0
    mode = (
        "static" if args.static else "resume" if args.resume else "continuous"
    )
    print(
        f"[{mode}] served {n_reqs} requests, {total_new} tokens, "
        f"{dt:.2f}s ({total_new / dt:.1f} tok/s, "
        f"per-token p50={p50 * 1e3:.1f}ms p95={p95 * 1e3:.1f}ms)"
    )
    if args.static:
        for i, o in enumerate(outs):
            print(f"  req{i}: {o.tolist()}")
    else:
        # continuous results are typed (RequestResult): summarize terminal
        # statuses so deadline expiry / load shedding is visible at a glance
        counts: dict[str, int] = {}
        for o in outs:
            counts[o.status.value] = counts.get(o.status.value, 0) + 1
        print("  statuses: " + ", ".join(
            f"{k}={v}" for k, v in sorted(counts.items())
        ))
        for i, o in enumerate(outs):
            why = f" ({o.reason})" if o.reason else ""
            print(f"  req{i} [{o.status.value}{why}]: {o.tolist()}")


if __name__ == "__main__":
    main()
