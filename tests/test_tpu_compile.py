"""Compile the serve path's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described and not attached.  Interpret mode cannot see what it
refuses (block shapes not aligned to the (8, 128) tiling, VMEM overruns),
so each case compiles one kernel at smollm-360m serving shapes and checks
that the Mosaic kernel (``tpu_custom_call``) is in the compiled program.

The topology is described inside a fixture only: describing it loads the
TPU library, which one process may hold at a time, so it must never happen
while a module is imported or tests are collected.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# smollm-360m: 15 query heads over 5 kv heads of width 64
KV, G, D = 5, 3, 64
SLOTS, MAX_LEN, BLOCK = 8, 2048, 16
D_MODEL, D_FF, VOCAB = 960, 2560, 49152


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
    assert "tpu_custom_call" in text


def test_paged_decode_compiles(spec):
    from repro.kernels.flash_attention.ops import decode_attention_paged

    n_blk = MAX_LEN // BLOCK
    pool = (SLOTS * n_blk + 1, BLOCK, KV, D)
    text = _compiled_text(
        lambda q, kp, vp, t, n: decode_attention_paged(
            q, kp, vp, t, n, impl="pallas", interpret=False
        ),
        spec((SLOTS, KV, G, D)),
        spec(pool),
        spec(pool),
        spec((SLOTS, n_blk), jnp.int32),
        spec((SLOTS,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_prefill_flash_attention_compiles(spec):
    from repro.kernels.flash_attention.ops import flash_attention

    text = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        spec((1, MAX_LEN, KV, G, D)),
        spec((1, MAX_LEN, KV, D)),
        spec((1, MAX_LEN, KV, D)),
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
