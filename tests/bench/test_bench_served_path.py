"""The served path against the float32 reference: the logits of the paged,
chunked ``Engine`` (scratch-lane chunks, install and pack into the pool,
paged decode) at every prompt position and every decoded position, on
seeded weights (CPU, tiny sizes).

The engine's own jitted programs run; the model's ``logits_fn``, which the
chunk and decode programs both call, is wrapped to hand each call's logits
to the host.  Cases: a granite-shaped size (8 query heads over 2 K/V heads
of 64, so a K/V row is 128 lanes, one token a pool row, ``r = 1``, and a
GQA group of 4) and a smollm-shaped size (6 over 2 heads of 32: 64 lanes,
two tokens a row, ``r = 2``).
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import drive, weights  # noqa: E402
from bench.reference import dense_llama  # noqa: E402

BS, CHUNK, MAX_LEN, SLOTS = 16, 32, 128, 2
PLEN, NEW = 75, 10      # three chunks, the last holding 11 tokens and 21 of padding
# float32 serving departs from the float32 reference only by the order of its
# sums (blockwise online softmax over pool blocks, fused GEMMs): 2.9e-6 and
# 3.8e-6 of the reference logits' spread at these sizes.  Serving in bfloat16
# rounds weights, activations and K/V to 8 bits of mantissa: 0.057 and 0.072.
# The limit lies more than a decade from each.
TOL = 1e-4

SHAPES = {
    "granite_r1": {"n_heads": 8, "n_kv_heads": 2, "head_dim": 64},
    "smollm_r2": {"n_heads": 6, "n_kv_heads": 2, "head_dim": 32},
}


def config(shape: dict, dtype: str) -> dict:
    return {
        "name": "served-path",
        "model": {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab": 256,
                  "rope_theta": 10000000.0, "norm_eps": 1e-05, "dtype": dtype, **shape},
        "serve": {"slots": SLOTS, "max_len": MAX_LEN, "block_size": BS, "num_blocks": 33,
                  "prefill_chunk": CHUNK, "token_budget": CHUNK},
    }


def served_logits(cfg: dict, params, prompt: np.ndarray):
    """Serve one greedy request through the paged, chunked engine.  Returns
    its tokens, the chunk logits at every prompt position, and the decode
    logits of its slot at each step."""
    from repro.serve import kvcache
    from repro.serve.engine import Engine, Request

    mcfg, scfg = drive.engine_config(cfg)
    eng = Engine(mcfg, params, scfg)
    assert eng.caches["kpool"].shape[-1] == kvcache.tokens_per_row(
        mcfg.n_kv_heads, mcfg.head_dim, BS) * mcfg.n_kv_heads * mcfg.head_dim
    seen = []
    cls = type(eng.model)
    inner = cls.logits_fn

    def logits_fn(self, params, x, positions=None, caches=None, remat=False):
        out = inner(self, params, x, positions=positions, caches=caches, remat=remat)
        jax.debug.callback(lambda lg: seen.append(np.asarray(lg, np.float32)), out[0],
                           ordered=True)
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cls, "logits_fn", logits_fn)
        rid = eng.submit(Request(prompt, max_new_tokens=NEW))
        while eng.step():
            pass
        jax.effects_barrier()
    toks = np.asarray(eng.pop_result(rid).tokens, np.int32)
    chunks = [lg[0] for lg in seen if lg.shape[1] == CHUNK]
    decodes = [lg[0, 0] for lg in seen if lg.shape[1] == 1]   # the request holds slot 0
    prefill = np.concatenate(chunks)[: len(prompt)]
    return toks, prefill, np.stack(decodes[: len(toks) - 1])


def widest_gap(shape: dict, dtype: str) -> float:
    """Largest |served - reference| logit over every compared position, as
    a share of the reference logits' spread."""
    cfg = config(shape, dtype)
    m = cfg["model"]
    params = weights.make(m, 2**31 + 17)
    prompt = np.random.default_rng(5).integers(0, m["vocab"], PLEN).astype(np.int32)
    toks, prefill, decode = served_logits(cfg, params, prompt)
    assert len(toks) == NEW
    seq = np.concatenate([prompt, toks[:-1]])
    ref = np.asarray(dense_llama.logits(params, m, jnp.asarray(seq)))
    got = np.concatenate([prefill, decode])
    assert got.shape == ref.shape
    return float(np.max(np.abs(got - ref)) / np.std(ref))


@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_paged_chunked_path_matches_reference(shape):
    assert widest_gap(SHAPES[shape], "float32") <= TOL
    assert widest_gap(SHAPES[shape], "bfloat16") > TOL
