"""Time the paged decode kernel alone, at a benchmark cell's shapes.

    PYTHONPATH=src python -m benchmarks.decode_kernel_micro \
        [--configs smollm-360m granite-8b-l18] [--reps 20] [--tiny]

For each configuration of ``bench/configs`` it builds random packed K/V
pools for every layer (bfloat16, as served), a batch of the cell's slots,
and one jitted program that runs the paged kernel as the decode program
serves it (``ops.decode_attention_paged``) once per layer over one work
list.  Two item mixes:

* ``chat``: the mix the cell's traced runs show (PERF.md §4-§5): a few
  live rows holding ``live_blocks`` blocks in all, every other slot a sink
  row at length 0, which still costs one work item;
* ``full``: every slot live at four blocks, which weighs the per-item body
  against the per-row first and last steps.

One JSON line per (configuration, mix): work items a layer, microseconds a
layer and an item (the median of ``--reps`` timed calls over the layer
count), and the largest gap between the kernel and its XLA twin on one
layer.  Run it from a checkout with ``PYTHONPATH`` at that checkout's
``src`` to time another version of the kernel with the same script.
On a host without a TPU the kernel runs in interpret mode, so ``--tiny``
(2 layers, 6 slots) is the only sensible size there, and its times are the
interpreter's.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.decode_attention import (
    decode_attention_paged_xla,
    paged_work_list,
)
from repro.kernels.flash_attention.ops import decode_attention_paged
from repro.serve.kvcache import SINK_BLOCK, tokens_per_row

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
# live blocks a layer in each cell's traced window (PERF.md §4-§5): smollm
# about 35 over a few rows, granite about 109 over about a dozen
MIXES = {
    "smollm-360m": dict(live_rows=4, live_blocks=35),
    "granite-8b-l18": dict(live_rows=12, live_blocks=109),
}


def _inputs(cfg: dict, mix: str, tiny: bool, seed: int = 0):
    m, s = cfg["model"], cfg["serve"]
    KV, G, d = m["n_kv_heads"], m["n_heads"] // m["n_kv_heads"], m["head_dim"]
    L, B, bs = m["n_layers"], s["slots"], s["block_size"]
    n_blk = s["max_len"] // bs
    if tiny:
        L, B, n_blk = 2, 6, 4
    if mix == "chat":
        live_rows = 2 if tiny else MIXES[cfg["name"]]["live_rows"]
        live_blocks = 5 if tiny else MIXES[cfg["name"]]["live_blocks"]
    else:
        live_rows, live_blocks = B, 4 * B
    rng = np.random.default_rng(seed)
    # a live row's blocks: the total split as evenly as the rows allow
    blocks = [live_blocks // live_rows + (i < live_blocks % live_rows)
              for i in range(live_rows)]
    lengths = np.zeros((B,), np.int32)
    tables = np.full((B, n_blk), SINK_BLOCK, np.int32)
    owned = rng.permutation(np.arange(1, live_blocks + 1))
    at = 0
    for b, n in zip(rng.permutation(B)[:live_rows], blocks):
        lengths[b] = (n - 1) * bs + int(rng.integers(1, bs + 1))
        tables[b, :n] = owned[at:at + n]
        at += n
    r = tokens_per_row(KV, d, bs)
    pool = (L, live_blocks + 1, bs // r, r * KV * d)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, KV, G, d), jnp.bfloat16)
    kp = jax.random.normal(jax.random.fold_in(key, 1), pool, jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 2), pool, jnp.bfloat16)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths), bs


def measure(name: str, mix: str, reps: int, tiny: bool) -> dict:
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    q, kp, vp, tables, lengths, bs = _inputs(cfg, mix, tiny)
    L = kp.shape[0]
    interpret = jax.default_backend() != "tpu"

    @jax.jit
    def layers(q, kp, vp, tables, lengths):
        work = paged_work_list(tables, lengths, bs)

        def body(li, acc):
            out = decode_attention_paged(
                q, kp, vp, tables, lengths, li, work=work, impl="pallas",
                interpret=interpret,
            )
            return acc + out.astype(jnp.float32)

        return jax.lax.fori_loop(0, L, body, jnp.zeros(q.shape, jnp.float32))

    args = (q, kp, vp, tables, lengths)
    layers(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        layers(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    n_work = int(paged_work_list(tables, lengths, bs)[1])
    layer_us = statistics.median(times) / L * 1e6

    li = jnp.int32(L // 2)
    got = jax.jit(
        lambda *a: decode_attention_paged(*a, impl="pallas", interpret=interpret)
    )(q, kp, vp, tables, lengths, li)
    want = decode_attention_paged_xla(q, kp, vp, tables, lengths, li)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    return {
        "config": name, "mix": mix, "device": jax.devices()[0].device_kind,
        "layers": L, "items_per_layer": n_work,
        "us_per_layer": round(layer_us, 3),
        "us_per_item": round(layer_us / n_work, 4),
        "max_abs_gap_vs_xla": gap,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--configs", nargs="+", default=sorted(MIXES))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args()
    for name in a.configs:
        for mix in ("chat", "full"):
            print(json.dumps(measure(name, mix, a.reps, a.tiny)), flush=True)


if __name__ == "__main__":
    main()
