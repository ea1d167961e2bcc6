"""Compile the serve path's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described and not attached.  Interpret mode cannot see what it
refuses (block shapes not aligned to the (8, 128) tiling, VMEM overruns),
so each case compiles one kernel at smollm-360m or granite-8b serving
shapes and checks that the Mosaic kernel (``tpu_custom_call``) is in the
compiled program, and the engine's programs at each benchmark cell's shapes.

The topology is described inside a fixture only: describing it loads the
TPU library, which one process may hold at a time, so it must never happen
while a module is imported or tests are collected.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))  # the checkout, for `bench`

# smollm-360m: 15 query heads over 5 kv heads of width 64
KV, G, D = 5, 3, 64
SLOTS, MAX_LEN, BLOCK = 8, 2048, 16
D_MODEL, D_FF, VOCAB = 960, 2560, 49152
# the benchmark's configuration files: the engine's programs and the paged
# and prefill kernels compile at each one's serving shapes
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))
CELLS = [pytest.param(json.loads(p.read_text()), id=p.stem) for p in CONFIGS]
# the v5e's device memory as its runtime reports it (``bytes_limit`` of
# ``memory_stats()``); a cell sizes its pool to 0.9 of it
BYTES_LIMIT = 16_909_336_064


def _heads(sizes: dict) -> tuple[int, int, int]:
    """(kv heads, query heads a kv head serves, head width) of a config file."""
    m = sizes["model"]
    return m["n_kv_heads"], m["n_heads"] // m["n_kv_heads"], m["head_dim"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    """Shape-only argument on the described chip (nothing is allocated)."""

    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sd


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_names(text: str) -> list[str]:
    """Instruction names of the Mosaic kernels in a compiled program: the
    text a profiler trace names each kernel's operation by."""
    return [
        line.split(" = ", 1)[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


@pytest.mark.parametrize("bk", [None, BLOCK], ids=["auto_bk", "bk16"])
def test_contiguous_decode_compiles(spec, bk):
    from repro.kernels.flash_attention.ops import decode_attention

    text = _compiled_text(
        lambda q, k, v, n: decode_attention(
            q, k, v, n, bk=bk, impl="pallas", interpret=False
        ),
        spec((SLOTS, KV, G, D)),
        spec((SLOTS, MAX_LEN, KV, D)),
        spec((SLOTS, MAX_LEN, KV, D)),
        spec((SLOTS,), jnp.int32),
    )
    assert _kernel_names(text) == ["decode_attention"]


@pytest.mark.parametrize("sizes", CELLS)
def test_paged_decode_compiles(spec, sizes):
    from repro.kernels.flash_attention.ops import decode_attention_paged
    from repro.serve.kvcache import tokens_per_row

    kv, g, d = _heads(sizes)
    slots, max_len, block = (sizes["serve"][k] for k in ("slots", "max_len", "block_size"))
    n_blk = max_len // block
    r = tokens_per_row(kv, d, block)
    pool = (2, slots * n_blk + 1, block // r, r * kv * d)
    text = _compiled_text(
        lambda q, kp, vp, t, n, li: decode_attention_paged(
            q, kp, vp, t, n, li, impl="pallas", interpret=False
        ),
        spec((slots, kv, g, d)),
        spec(pool),
        spec(pool),
        spec((slots, n_blk), jnp.int32),
        spec((slots,), jnp.int32),
        spec((), jnp.int32),
    )
    assert _kernel_names(text) == ["decode_attention_paged"]


@pytest.mark.parametrize("sizes", CELLS)
def test_paged_decode_scratch_fits_vmem(sizes):
    """The paged kernel's VMEM scratch at each cell's heads: the
    block-diagonal query and the accumulator, ``(r*KV*G, r*KV*d)`` each,
    with the running max and normalizer, padded to the chip's tiles, fit
    with the double-buffered K/V blocks in the v5e's default 16 MiB of
    scoped VMEM."""
    from repro.kernels.flash_attention.decode_attention import _decode_scratch
    from repro.serve.kvcache import tokens_per_row

    kv, g, d = _heads(sizes)
    block = sizes["serve"]["block_size"]
    r = tokens_per_row(kv, d, block)
    q = jax.ShapeDtypeStruct((1, kv, g, d), jnp.bfloat16)
    scratch = _decode_scratch(q, r)
    assert [tuple(x.shape) for x in scratch] == [
        (r * kv * g, r * kv * d), (r * kv * g, 1), (r * kv * g, 1),
        (r * kv * g, r * kv * d),
    ]

    def tiled(shape, dtype):
        rows = 8 * 4 // jnp.dtype(dtype).itemsize   # (8, 128) 32-bit words
        return (-(-shape[0] // rows) * rows) * (-(-shape[1] // 128) * 128) * (
            jnp.dtype(dtype).itemsize
        )

    used = sum(tiled(x.shape, x.dtype) for x in scratch)
    kv_blocks = 2 * 2 * tiled((block // r, r * kv * d), jnp.bfloat16)
    assert used + kv_blocks < 16 * 2**20, (used, kv_blocks)


def test_engine_decode_program_names_its_kernel(one_chip, monkeypatch):
    """The serve engine's whole paged decode program (``jit_decode_fn``), as
    the chip runs it: the paged kernel keeps its name through inlining, so
    a trace can find it by name rather than as the program's only kernel."""
    from repro.arch.model_zoo import build
    from repro.configs.registry import get
    from repro.serve.engine import Engine, KVConfig, SchedulerConfig, ServeConfig

    cfg = get("smollm-360m-smoke")
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(
        max_len=256,
        scheduler=SchedulerConfig(batch=SLOTS),
        kv=KVConfig(layout="paged", block_size=BLOCK),
    ))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    i32 = jnp.zeros((SLOTS,), jnp.int32)
    # the engine picks the Pallas kernel when JAX's backend is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered = eng._decode.lower(
        on_chip(params), on_chip(i32[:, None]), on_chip(eng.caches),
        on_chip(jnp.zeros((SLOTS, 2), jnp.uint32)), on_chip(i32),
    )
    monkeypatch.undo()
    text = lowered.compile().as_text()
    assert _kernel_names(text) == ["decode_attention_paged"]


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8}
_ARRAY = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_OPCODE = re.compile(r"(?<=[\s}\]])([a-z][a-z0-9-]*)\(")


def _largest_array(shape: str) -> tuple[int, tuple[int, ...]]:
    """(bytes, dims) of the largest array in an HLO result type (a tuple's
    elements are separate buffers)."""
    arrays = [(0, ())]
    for dtype, dims in _ARRAY.findall(shape):
        dims = tuple(int(x) for x in dims.split(",") if x)
        n = _BYTES.get(dtype, 1)
        for x in dims:
            n *= x
        arrays.append((n, dims))
    return max(arrays)


def _moves(text: str) -> list[tuple[str, str, int, tuple[int, ...]]]:
    """(instruction, opcode, bytes moved, their dims) of every copy,
    dynamic-slice and dynamic-update-slice in a compiled program, fused
    ones included.  A copy or slice moves its result; an in-place
    dynamic-update-slice's result is the buffer it updates, so what it
    moves is its update (operand 1)."""
    shapes, found = {}, []
    for line in text.splitlines():
        if " = " not in line:
            continue
        name, rest = line.split(" = ", 1)
        name = name.split()[-1].lstrip("%")
        m = _OPCODE.search(rest)
        if m is None:
            continue
        shapes[name] = rest[: m.start()]
        op = m.group(1)
        if op in ("copy", "copy-start", "dynamic-slice"):
            found.append((name, op, name))
        elif op == "dynamic-update-slice":
            operands = rest[m.end():].split(")", 1)[0].split(",")
            found.append((name, op, operands[1].split("*/")[-1].strip().lstrip("%")))
    return [
        (name, op, *_largest_array(shapes.get(ref, ""))) for name, op, ref in found
    ]


@pytest.mark.parametrize("sizes", CELLS)
def test_engine_paged_programs_keep_the_pool_in_place(one_chip, monkeypatch, sizes):
    """At a benchmark cell's shapes (its configuration file's layers, widths,
    slots, blocks and max_len) the engine's decode, block-pack and
    copy-on-write programs move no buffer the size of a layer's KV pool:
    the decode program carries the stacked pools through its layer loop and
    the kernel reads them in place.

    Weights and pool fit 0.9 of the chip's memory, the rule each cell's pool
    is sized by, and with every other live buffer as compiled here (the
    prefill scratch, the decode and chunk programs' temporaries) the whole
    of it.  The 0.9 rule is not applied to the compiled temporaries: they
    are not what the chip holds.  At granite-8b's widths the chunk program's
    compile to 0.59 GB where the v5e's peak shows about 0.33 GB, so the
    chip's own peak (0.8998 of the limit) is what sizes the pool.  Only
    shapes are given; nothing is allocated at the cell's size."""
    from bench import drive
    from repro.arch.model_zoo import build
    from repro.serve import kvcache
    from repro.serve.engine import Engine

    cfg, scfg = drive.engine_config(sizes)
    slots, max_len, bs = scfg.batch, scfg.max_len, scfg.block_size
    blocks = scfg.num_blocks
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, dataclasses.replace(
        scfg, kv=dataclasses.replace(scfg.kv, num_blocks=4)))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    caches = on_chip(jax.eval_shape(
        lambda: kvcache.build_paged_caches(cfg, slots, max_len, blocks, bs)
    ))
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    r = kvcache.tokens_per_row(kv, hd, bs)
    assert caches["kpool"].shape == (cfg.n_layers, blocks, bs // r, r * kv * hd)
    layer_pool = blocks * bs * kv * hd * 2
    scratch = on_chip(jax.eval_shape(eng._fresh_scratch))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    rows = on_chip(jnp.zeros((slots,), jnp.int32))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode = eng._decode.lower(
        on_chip(params), on_chip(jnp.zeros((slots, 1), jnp.int32)), caches,
        on_chip(jnp.zeros((slots, 2), jnp.uint32)), rows,
    ).compile()
    chunk = eng._chunk_step.lower(
        on_chip(params), on_chip(jnp.zeros((1, scfg.prefill_chunk), jnp.int32)), scratch,
        on_chip(jnp.zeros((1,), jnp.int32)), on_chip(jnp.zeros((2,), jnp.uint32)),
    ).compile()
    pack = eng.kv.pack_row.lower(
        caches, {"k": scratch["k"], "v": scratch["v"]}, i32, i32,
        on_chip(jnp.zeros((4,), jnp.int32)),
    ).compile()
    cow = eng.kv.copy_block.lower(caches, i32, i32, i32, i32).compile()
    monkeypatch.undo()

    # a weight's relayout (the tied embedding, read by the gather and the
    # head) is not a pool move; every other move that big is
    weights = {tuple(sorted(a.shape)) for a in jax.tree.leaves(params)}
    for prog in (decode, pack, cow):
        big = [
            m for m in _moves(prog.as_text())
            if m[2] >= layer_pool and tuple(sorted(m[3])) not in weights
        ]
        assert not big, big
    assert decode.memory_analysis().temp_size_in_bytes < 1 << 30
    assert _kernel_names(decode.as_text()) == ["decode_attention_paged"]

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    held = nbytes(params) + nbytes(caches)
    assert held <= 0.9 * BYTES_LIMIT, held
    temps = sum(p.memory_analysis().temp_size_in_bytes for p in (decode, chunk))
    assert held + nbytes(scratch) + temps <= BYTES_LIMIT, (held, nbytes(scratch), temps)


@pytest.mark.parametrize("sizes", CELLS)
def test_prefill_flash_attention_compiles(spec, sizes):
    from repro.kernels.flash_attention.ops import flash_attention

    kv, g, d = _heads(sizes)
    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        spec((1, MAX_LEN, kv, g, d)),
        spec((1, MAX_LEN, kv, d)),
        spec((1, MAX_LEN, kv, d)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [D_FF, VOCAB], ids=["mlp_up", "unembed"])
def test_mapper_tiled_matmul_compiles(spec, n):
    from repro.kernels.matmul.ops import matmul

    text = _compiled_text(
        lambda a, b: matmul(a, b, interpret=False),
        spec((SLOTS, D_MODEL)),
        spec((D_MODEL, n)),
    )
    assert "tpu_custom_call" in text


def test_abft_matmul_compiles(spec):
    """The checksum-carrying matmul the ABFT decode path routes GEMMs
    through (KernelConfig(matmul="pallas", abft=...))."""
    from repro.kernels.matmul.ops import matmul_abft

    text = _compiled_text(
        lambda a, b: matmul_abft(a, b, interpret=False),
        spec((SLOTS, D_MODEL)),
        spec((D_MODEL, D_FF)),
    )
    assert "tpu_custom_call" in text
