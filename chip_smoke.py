"""Drive the serve path once on one TPU chip at full smollm-360m width.

    python3 chip_smoke.py [--seed N]

Runs in one process and needs exactly the chip JAX finds; off a TPU it
exits non-zero before doing any work.  Phases, each printing its lines:

  1. device   platform, device kind and count as JAX reports them.
  2. kernels  the Pallas kernels at serve shapes against float32
              references: contiguous flash-decoding (S 2048), paged
              flash-decoding (block 16, plus its bitwise contract with the
              contiguous kernel at bk == block), prefill flash attention,
              and the mapper-tiled matmul.
  3. serve    smollm-360m (32 layers, random weights from --seed) through
              serve.engine.Engine twice: the default contiguous engine,
              then the paged engine with chunked prefill.  Every request
              must finish with its full token count, with no substrate
              fallback and no non-finite quarantine.

Compile and wall times are printed as set-up information, not as speed
results.  Any failed phase exits non-zero; on success the last line is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache, pin_repro_caches  # noqa: E402

ARCH = "smollm-360m"
SLOTS, MAX_LEN, BLOCK, CHUNK = 8, 2048, 16, 256
N_REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 32, (100, 1500)
# admission prefills pad prompts to this bucket: 100..1500-token prompts
# compile at most three admission shapes (512, 1024, 1536)
PREFILL_BUCKET = 512
# bf16 kernel output vs a float32 reference: |got - ref| <= ATOL + RTOL*|ref|
ATOL = RTOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the backend compile time JAX reports inside its ``with``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs = 0.0

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.secs += secs

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


# ------------------------------------------------------------------ device


def device_info() -> dict:
    dev = jax.devices()[0]
    info = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(
        f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}",
        flush=True,
    )
    _check(info["platform"] == "tpu", f"no TPU: JAX found {info['platform']}")
    return info


# ----------------------------------------------------------------- kernels


def _report(name: str, got, ref) -> None:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    _check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = np.abs(got - ref)
    ok = bool((err <= ATOL + RTOL * np.abs(ref)).all())
    print(
        f"[kernel] {name}: max_abs_err={float(err.max())!r} "
        f"tol=atol {ATOL} + rtol {RTOL}*|ref| "
        f"{'ok' if ok else 'FAILED'}",
        flush=True,
    )
    _check(ok, f"{name}: outside tolerance of its float32 reference")


def kernel_checks(
    seed: int,
    *,
    slots: int = SLOTS,
    max_len: int = MAX_LEN,
    block: int = BLOCK,
    kv: int = 5,
    g: int = 3,
    d: int = 64,
    d_model: int = 960,
    widths: tuple[int, ...] = (2560, 49152),
) -> None:
    """Each serve-path kernel against its float32 reference; shapes
    default to smollm-360m serving (15 query heads over 5 kv heads)."""
    from repro.kernels.flash_attention.ops import (
        decode_attention,
        decode_attention_paged,
        flash_attention,
    )
    from repro.kernels.flash_attention.ref import (
        decode_attention_paged_ref,
        decode_attention_ref,
        flash_attention_ref,
    )
    from repro.kernels.matmul.ops import matmul

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape, f32)).astype(bf16)

    def ref(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*(a.astype(f32) for a in args), **kw)

    # ---- decode: contiguous cache and the same keys in a shuffled pool
    q = normal((slots, kv, g, d))
    k = normal((slots, max_len, kv, d))
    v = normal((slots, max_len, kv, d))
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, max_len + 1, slots)
    lens[0], lens[-1] = 1, max_len
    lens = jnp.asarray(lens, jnp.int32)
    got = decode_attention(q, k, v, lens)
    _report(
        f"decode_contiguous S={max_len}", got,
        ref(decode_attention_ref, q, k, v, lengths=lens),
    )

    n_blk = max_len // block
    phys = rng.permutation(slots * n_blk) + 1       # block 0 is the sink
    tables = jnp.asarray(phys.reshape(slots, n_blk), jnp.int32)

    def pool_of(x):
        blocks = x.reshape(slots * n_blk, block, kv, d)
        pool = jnp.zeros((slots * n_blk + 1, block, kv, d), x.dtype)
        return pool.at[jnp.asarray(phys)].set(blocks)

    kpool, vpool = pool_of(k), pool_of(v)
    got = decode_attention_paged(q, kpool, vpool, tables, lens)
    _report(
        f"decode_paged block={block}", got,
        ref(decode_attention_paged_ref, q, kpool, vpool, tables=tables,
            lengths=lens),
    )
    same = decode_attention(q, k, v, lens, bk=block)
    bitwise = bool(np.array_equal(np.asarray(got), np.asarray(same)))
    print(
        f"[kernel] decode_paged == decode_contiguous at bk={block}: "
        f"{'bitwise' if bitwise else 'DIFFERS'}",
        flush=True,
    )
    _check(bitwise, "paged and contiguous decode differ at bk == block")

    # ---- prefill flash attention (causal, GQA resolved in the kernel)
    qp = normal((1, max_len, kv, g, d))
    kp = normal((1, max_len, kv, d))
    vp = normal((1, max_len, kv, d))
    got = flash_attention(qp, kp, vp)
    rows = qp[0].transpose(1, 2, 0, 3).reshape(kv * g, max_len, d)
    krows = jnp.repeat(kp[0].transpose(1, 0, 2), g, axis=0)
    vrows = jnp.repeat(vp[0].transpose(1, 0, 2), g, axis=0)
    want = ref(flash_attention_ref, rows, krows, vrows)
    want = want.reshape(kv, g, max_len, d).transpose(2, 0, 1, 3)[None]
    _report(f"prefill_flash T={max_len}", got, want)

    # ---- mapper-tiled matmul (decode GEMM shapes)
    a = normal((slots, d_model))
    for n in widths:
        w = normal((d_model, n), scale=d_model ** -0.5)
        _report(
            f"matmul {slots}x{d_model}x{n}", matmul(a, w),
            ref(jnp.matmul, a, w),
        )


# ------------------------------------------------------------------- serve


def make_requests(cfg, seed: int, n: int, prompt_lens, new_tokens: int):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [
        Request(
            rng.integers(0, cfg.vocab, int(rng.integers(lo, hi + 1))).astype(
                np.int32
            ),
            max_new=new_tokens,
            request_id=i,
        )
        for i in range(n)
    ]


def serve_once(name, cfg, params, scfg, reqs, clock: CompileClock):
    """Serve ``reqs`` on a fresh engine; returns each request's tokens."""
    from repro.serve.engine import Engine, RequestStatus

    c0, t0 = clock.secs, time.perf_counter()
    with Engine(cfg, params, scfg) as eng:
        outs = eng.run(reqs)
        stats = dict(eng.stats)
    wall = time.perf_counter() - t0
    del eng
    gc.collect()

    toks = [o.tolist() for o in outs]
    finished = sum(o.status == RequestStatus.FINISHED for o in outs)
    print(
        f"[serve] {name}: {finished}/{len(reqs)} FINISHED, "
        f"{sum(map(len, toks))} tokens, fallbacks={stats['fallbacks']} "
        f"quarantined={stats['quarantined']}; set-up info, not a speed "
        f"result: compile {clock.secs - c0!r}s, wall {wall!r}s",
        flush=True,
    )
    for r, o, t in zip(reqs, outs, toks):
        _check(
            o.status == RequestStatus.FINISHED,
            f"{name}: request {r.request_id} ended {o.status.value} "
            f"({o.reason})",
        )
        _check(
            len(t) == r.max_new,
            f"{name}: request {r.request_id} emitted {len(t)}/{r.max_new}",
        )
        _check(
            all(0 <= x < cfg.vocab for x in t),
            f"{name}: request {r.request_id} emitted an out-of-vocab token",
        )
    _check(stats["fallbacks"] == 0, f"{name}: decode fell back off the kernel")
    _check(stats["quarantined"] == 0, f"{name}: non-finite logits quarantined")
    return toks


def serve_checks(
    cfg,
    seed: int,
    clock: CompileClock,
    *,
    slots: int = SLOTS,
    max_len: int = MAX_LEN,
    block: int = BLOCK,
    chunk: int = CHUNK,
    bucket: int = PREFILL_BUCKET,
    n_requests: int = N_REQUESTS,
    prompt_lens: tuple[int, int] = PROMPT_LENS,
    new_tokens: int = NEW_TOKENS,
) -> None:
    """Both KV layouts of the engine over one seeded greedy workload."""
    from repro.arch.model_zoo import build
    from repro.serve.engine import (
        DurabilityConfig,
        KVConfig,
        SchedulerConfig,
        ServeConfig,
    )

    params = build(cfg).init(jax.random.PRNGKey(seed))
    reqs = make_requests(cfg, seed, n_requests, prompt_lens, new_tokens)
    strict = DurabilityConfig(substrate_fallback=False)
    contiguous = ServeConfig(
        max_len=max_len,
        temperature=0.0,
        seed=seed,
        scheduler=SchedulerConfig(batch=slots, prefill_bucket=bucket),
        durability=strict,
    )
    paged = ServeConfig(
        max_len=max_len,
        temperature=0.0,
        seed=seed,
        scheduler=SchedulerConfig(batch=slots, prefill_chunk=chunk),
        kv=KVConfig(layout="paged", block_size=block),
        durability=strict,
    )
    a = serve_once("contiguous", cfg, params, contiguous, reqs, clock)
    b = serve_once(
        f"paged block={block} prefill_chunk={chunk}",
        cfg, params, paged, reqs, clock,
    )
    same = sum(x == y for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    print(
        f"[serve] paged-vs-contiguous token agreement: "
        f"{same / sum(map(len, a))!r} (information only: the layouts "
        f"reduce attention in different orders)",
        flush=True,
    )


# -------------------------------------------------------------------- main


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, inputs and prompts")
    args = ap.parse_args(argv)

    pin_repro_caches()
    enable_compile_cache()
    phase = "device"
    try:
        info = device_info()
        from repro.configs.registry import get

        phase = "kernels"
        kernel_checks(args.seed)
        phase = "serve"
        with CompileClock() as clock:
            serve_checks(get(ARCH), args.seed, clock)
    except Exception as e:  # report the failed phase, then exit non-zero
        traceback.print_exc()
        print(f"[{phase}] FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
