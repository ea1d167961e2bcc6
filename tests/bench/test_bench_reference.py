"""The float32 reference against the program's prefill and decode logits,
and the float8 control that the check must reject."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import drive, weights  # noqa: E402
from bench.reference import dense_llama  # noqa: E402

TINY = json.loads((Path(__file__).parent / "tiny.json").read_text())


def program(cfg):
    from repro.arch.model_zoo import build

    mcfg, _ = drive.engine_config(cfg)
    return build(mcfg)


def f32_cfg():
    cfg = json.loads(json.dumps(TINY))
    cfg["model"]["dtype"] = "float32"
    return cfg


def test_weights_have_the_served_layout():
    from bench.run import check_params

    mcfg, _ = drive.engine_config(TINY)
    check_params(weights.make(TINY["model"], 2**31 + 3), mcfg)


def test_weights_follow_the_seed():
    a = weights.make(TINY["model"], 2**32 + 5)
    b = weights.make(TINY["model"], 2**32 + 5)
    c = weights.make(TINY["model"], 5)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["embed"]["tok"]), np.asarray(c["embed"]["tok"]))


def test_reference_matches_program_prefill_and_decode_in_float32():
    cfg = f32_cfg()
    m = cfg["model"]
    params = weights.make(m, 11)
    model = program(cfg)
    T = 37
    toks = np.random.default_rng(0).integers(0, m["vocab"], T).astype(np.int32)
    ref = np.asarray(dense_llama.logits(params, m, jnp.asarray(toks)))
    with jax.default_matmul_precision("highest"):
        caches = model.init_caches(1, 64)
        last, caches = model.prefill(params, jnp.asarray(toks[None, :20]), caches)
        got = [np.asarray(last[0])]
        for t in range(20, T):
            logits, caches = model.decode_step(params, jnp.asarray(toks[None, t : t + 1]), caches)
            got.append(np.asarray(logits[0]))
    got = np.stack(got)
    np.testing.assert_allclose(got, ref[19:T], atol=2e-5, rtol=2e-5)


def test_control_precision_fails_where_float32_passes():
    cfg = f32_cfg()
    m = cfg["model"]
    params = weights.make(m, 12)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, m["vocab"], 48).astype(np.int32))
    ref = dense_llama.logits(params, m, toks)
    again = dense_llama.logits(params, m, toks)
    ctl = dense_llama.logits(params, m, toks, quant="fp8")
    spread = float(jnp.std(ref))
    assert float(jnp.max(jnp.abs(again - ref))) == 0.0
    assert float(jnp.max(jnp.abs(ctl - ref))) > 0.3 * spread


@pytest.mark.parametrize("n_layers", [1, 3])
def test_reference_depth_follows_the_config(n_layers):
    cfg = f32_cfg()
    cfg["model"]["n_layers"] = n_layers
    params = weights.make(cfg["model"], 1)
    out = dense_llama.logits(params, cfg["model"], jnp.arange(9, dtype=jnp.int32))
    assert out.shape == (9, cfg["model"]["vocab"]) and bool(jnp.all(jnp.isfinite(out)))
