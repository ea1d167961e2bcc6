"""Launch/dry-run machinery tests: spec builders, HLO collective parser,
cell accounting, and one real (subprocess) dry-run cell."""

import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import get
from repro.launch.dryrun import collective_bytes, scan_unit, variant_cfg
from repro.launch.specs import (
    cell_is_live,
    choose_microbatches,
    input_specs,
    live_cells,
    params_shapes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_live_cells_count():
    cells = live_cells()
    # 10 archs x 4 shapes - 7 long_500k skips (only gemma3/rwkv6/
    # recurrentgemma are sub-quadratic) = 33
    assert len(cells) == 33
    longs = [a for a, s in cells if s == "long_500k"]
    assert sorted(longs) == ["gemma3-12b", "recurrentgemma-2b",
                             "rwkv6-1.6b"]


def test_collective_parser():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256] %x), replica_groups={}
  %ag.1 = bf16[64,512]{1,0} all-gather-start(bf16[64,32] %y)
  %cp = u8[1024]{0} collective-permute(u8[1024] %z)
  %notacoll = f32[4]{0} add(f32[4] %a, f32[4] %b)
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 2 * 128 * 256 * 4
    assert got["all-gather"] == 64 * 512 * 2
    assert got["collective-permute"] == 1024
    assert got["total"] == sum(
        got[c] for c in
        ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
    )


def test_microbatch_choice_bounds_memory():
    cfg = get("grok-1-314b")
    mb = choose_microbatches(cfg, SHAPES["train_4k"], n_dp=32)
    b_local = 256 // 32
    resid = cfg.n_layers * (b_local // mb) * 4096 * cfg.d_model * 2
    # fits the budget, or microbatching is already maxed (1 seq/device)
    assert resid <= 2 * 1024**3 or mb == b_local
    # small model needs no microbatching
    assert choose_microbatches(get("smollm-360m"), SHAPES["train_4k"], 32) <= 2


@pytest.mark.parametrize("arch", ["granite-8b", "whisper-medium",
                                  "llava-next-34b", "rwkv6-1.6b"])
def test_input_specs_shapes(arch):
    sp = input_specs(arch, "train_4k", n_dp=32)
    tok = sp["batch"]["tokens"]
    assert tok.shape[0] * tok.shape[1] == 256  # mb x bm == global batch
    assert tok.shape[2] == 4096
    cfg = get(arch)
    if cfg.family == "encdec":
        assert sp["batch"]["frames"].shape[-2] == cfg.encoder_seq
    if cfg.family == "vlm":
        assert sp["batch"]["patches"].shape[-2] == cfg.n_patches

    spd = input_specs(arch, "decode_32k", n_dp=32)
    assert spd["batch"]["tokens"].shape == (128, 1)
    assert "caches" in spd


def test_variant_cfg_scales_layers():
    cfg = get("granite-8b")
    assert variant_cfg(cfg, 2, scan_unit(cfg)).n_layers == 2
    w = get("whisper-medium")
    v = variant_cfg(w, 1, scan_unit(w))
    assert v.n_layers == 1 and v.encoder_layers == 1
    h = get("recurrentgemma-2b")
    u = (h.rnn_per_attention + 1)
    assert variant_cfg(h, 2, u).n_layers == 2 * u


def test_params_shapes_no_allocation():
    import math

    shapes = params_shapes(get("grok-1-314b"))
    total = sum(
        math.prod(l.shape) for l in jax.tree.leaves(shapes)
    )
    assert total > 250e9  # ~314B params without ever allocating


def test_model_flops_sanity():
    from benchmarks.roofline import model_flops

    train = model_flops("granite-8b", "train_4k")
    prefill = model_flops("granite-8b", "prefill_32k")
    decode = model_flops("granite-8b", "decode_32k")
    assert train > prefill > decode > 0
    # MoE active < dense at same scale
    g = get("grok-1-314b")
    assert g.active_params_count() < g.params_count() / 2


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    """Real dry-run cell end-to-end (512 fake devices in a subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-360m", "--shape", "prefill_32k",
         "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.load(
        open(tmp_path / "smollm-360m__prefill_32k__16x16.json")
    )
    assert rec["cost_per_device"]["flops"] > 0
    assert rec["memory"]["peak_estimate_bytes"] < 16 * 1024**3


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The entry points' compile cache honours $JAX_COMPILATION_CACHE_DIR
    and otherwise sits at one fixed, git-ignored path of the checkout."""
    from pathlib import Path

    from repro.launch.cache import compile_cache_dir, enable_compile_cache

    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    assert compile_cache_dir(env) == tmp_path
    default = compile_cache_dir({})
    assert default == compile_cache_dir({}) == Path(REPO) / ".cache" / "jax"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()

    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        # set: JAX reads the variable itself, the entry point sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == str(default)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()
