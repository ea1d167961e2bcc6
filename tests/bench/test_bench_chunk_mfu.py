"""``chunk_mfu.open``: the prefill chunk program's share of its roofline, by
hand arithmetic on hand-built spans and chunk executions."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import types  # noqa: E402

import pytest  # noqa: E402

from bench import spec  # noqa: E402
from bench.drive import Step  # noqa: E402
from bench.metrics_ctx import Context  # noqa: E402
from bench.trace import Event, Trace, _attribute  # noqa: E402

MS = 1_000_000
# layer 8*4*2 + 2*8*2*2 + 4*2*8 + 3*8*16 = 576 parameters, body 1152, head 80;
# weights (1152 + 80) * 2 = 2464 bytes; K/V 2*2*2*2*2 = 32 bytes a token;
# attention 4*2*4*2 = 64 operations a key
MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
         "d_ff": 16, "vocab": 10}
PEAK = {"flops": 3.5e6, "hbm_bytes_per_s": 1e6}


def ev(a, b, name, **stats):
    return Event(int(a * MS), int(b * MS), name, stats)


def synthetic(with_start: bool = True):
    """A 100 ms window with two engine steps, each running one chunk: prompt
    tokens [0, 4) in step 0 (chunk program 8-11 ms) and the prompt's last
    three tokens [4, 7), padded to the chunk's width, in step 1 (60-65 ms).
    A third chunk (82-84 ms) runs outside any traced step."""
    def chunk(a, b, start, filled):
        stats = {"rid": 3, "filled": filled, **({"start": start} if with_start else {})}
        return ev(a, b, "serve.lane.chunk", **stats)

    mods = [ev(8, 11, "jit_chunk_fn"), ev(14, 30, "jit_decode_fn"),
            ev(60, 65, "jit_chunk_fn"), ev(66, 70, "jit_decode_fn"), ev(82, 84, "jit_chunk_fn")]
    ops = [ev(m.start / MS, m.end / MS, "%fusion.1 = bf16[1,8]{1,0} fusion(%p)") for m in mods]
    _attribute(ops, mods)
    spans = [ev(0, 100, "bench.window"),
             ev(5, 35, "bench.step", step=0), ev(5, 12, "serve.schedule"), chunk(7, 11, 0, 4),
             ev(55, 75, "bench.step", step=1), ev(55, 60, "serve.schedule"), chunk(57, 60, 4, 7),
             ev(80, 81, "bench.idle"), chunk(81, 82, 7, 9)]
    return Trace(ops, mods, sorted(spans, key=lambda e: e.start), 1)


def context(tr):
    steps = [Step(0, 0.0, decode_lens=[3, 5]), Step(1, 0.0, decode_lens=[4, 6], prompt_lens=[7])]
    drv = types.SimpleNamespace(records={}, steps=steps)
    cell = types.SimpleNamespace(config={"model": MODEL})
    return Context(tr, drv, cell, 0.0, 1.0, PEAK)


def test_chunk_cost_by_hand():
    from bench.metrics.chunk_mfu import chunk_cost

    # 4 tokens over keys 1+2+3+4 = 10: 2*1152*4 + 64*10 + 2*80; weights and 4 tokens of K/V
    assert chunk_cost(MODEL, 0, 4) == (10016.0, 2592.0)
    # 3 tokens after 4 over keys 5+6+7 = 18: 2*1152*3 + 64*18 + 2*80; 7 tokens of K/V
    assert chunk_cost(MODEL, 4, 7) == (8224.0, 2688.0)


def test_chunk_share_by_hand():
    got = spec.reader("chunk_mfu.open")(context(synthetic()))
    # chunk 0 is bound by compute (10016 / 3.5e6 s > 2592 / 1e6 s), chunk 1 by
    # memory (8224 / 3.5e6 s < 2688 / 1e6 s); the programs ran 3 + 5 ms; the
    # chunk outside the traced steps counts on neither side
    least = 10016 / 3.5e6 + 2688 / 1e6
    assert got == pytest.approx(100 * least / 0.008)


def test_no_reading_without_start():
    assert spec.reader("chunk_mfu.open")(context(synthetic(with_start=False))) is None
