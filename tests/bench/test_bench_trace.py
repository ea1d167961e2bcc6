"""Trace reduction: intervals, idle gaps, the breakdown and the readers."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import types  # noqa: E402

import pytest  # noqa: E402

from bench import spec  # noqa: E402
from bench.drive import Step  # noqa: E402
from bench.metrics_ctx import Context  # noqa: E402
from bench.trace import KERNEL, Event, Trace, _attribute, breakdown, busy_ns, gaps, union  # noqa: E402

MS = 1_000_000
MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
         "d_ff": 16, "vocab": 10}
PEAK = {"flops": 1e12, "hbm_bytes_per_s": 1e9}


def ev(a, b, name, **stats):
    return Event(a * MS, b * MS, name, stats)


def synthetic():
    """A 100 ms window with two engine steps; the device runs a decode
    program (with its attention kernel inside) in each, and a prefill chunk
    in the first."""
    kernel = f"%closed_call.11 = bf16[64,5,8,64]{{3,2,1,0}} custom-call(%a), {KERNEL}"
    ops = [
        ev(10, 20, "%fusion.1 = bf16[1,512,960]{1,0} fusion(%p)"),
        ev(22, 30, "%while.2 = (s32[], bf16[64,1,960]) while(%t)"),
        ev(22, 24, "%copy.80 = bf16[1,1101,128,5,64]{2,4,3,1,0} copy(%x)"),
        ev(24, 26, kernel),
        ev(60, 70, "%while.2 = (s32[], bf16[64,1,960]) while(%t)"),
        ev(60, 62, "%copy.80 = bf16[1,1101,128,5,64]{2,4,3,1,0} copy(%x)"),
        ev(62, 66, kernel),
    ]
    mods = [ev(10, 20, "jit_chunk_fn"), ev(22, 30, "jit_decode_fn"),
            ev(60, 70, "jit_decode_fn")]
    _attribute(ops, mods)
    spans = [ev(0, 100, "bench.window"), ev(5, 35, "bench.step", step=0),
             ev(35, 55, "bench.idle"), ev(55, 75, "bench.step", step=1)]
    return Trace(ops, mods, spans, 1)


def ctx(tr):
    steps = [Step(0, 0.0, decode_lens=[3, 5], prompt_lens=[40]), Step(1, 0.0, decode_lens=[4, 6])]
    drv = types.SimpleNamespace(records={}, steps=steps)
    cell = types.SimpleNamespace(config={"model": MODEL})
    return Context(tr, drv, cell, 0.0, 1.0, PEAK)


def test_union_merges_and_clips():
    assert union([(0, 10), (5, 15), (20, 30)], 0, 100) == 25
    assert union([(0, 10), (5, 15), (20, 30)], 8, 25) == 12
    assert union([], 0, 10) == 0


def test_busy_gaps_and_breakdown():
    tr = synthetic()
    lo, hi = tr.window()
    assert busy_ns(tr, lo, hi) == 28 * MS
    assert gaps(tr, lo, hi) == [(0, 10 * MS), (20 * MS, 22 * MS), (30 * MS, 60 * MS),
                                (70 * MS, 100 * MS)]
    b = breakdown(tr, lo, hi)
    assert b["device_ops"][0] == ["jit_chunk_fn/%fusion.1 bf16[1,512,960]", pytest.approx(0.010)]
    assert ["jit_decode_fn/%closed_call.11 bf16[64,5,8,64]", pytest.approx(0.006)] in b["device_ops"]
    assert not any("while" in k for k, _ in b["device_ops"])
    labels = dict((round(s * 1e3), label) for label, s in b["idle_gaps"])
    assert labels[30] == "bench.idle" or labels[30] == "none"
    assert ["bench.step", pytest.approx(0.002)] in b["idle_gaps"]


def read(name, tr):
    return spec.reader(name)(ctx(tr))


def test_readers_on_the_synthetic_trace():
    from bench import cost

    tr = synthetic()
    assert read("idle_share.open", tr) == pytest.approx(72.0)
    # step 0: 30 ms span, 18 ms busy; step 1: 20 ms span, 10 ms busy
    assert read("host_gap_ms.open", tr) == pytest.approx((12 + 10) / 2)
    assert read("decode_step_ms.open", tr) == pytest.approx(9.0)
    assert read("prefill_ms_per_ktok.open", tr) == pytest.approx(10.0 / 40 * 1000)
    least = sum(cost.least_time(*cost.decode_attention(MODEL, lens), PEAK)[0]
                for lens in ([3, 5], [4, 6]))
    assert read("attn_roofline.open", tr) == pytest.approx(100 * least / 0.006)
    least = sum(cost.least_time(*cost.decode_step(MODEL, lens), PEAK)[0]
                for lens in ([3, 5], [4, 6]))
    assert read("step_mfu.open", tr) == pytest.approx(100 * least / 0.018)
    flops = cost.prompt_flops(MODEL, 40) + sum(cost.output_flops(MODEL, k) for k in (3, 5, 4, 6))
    assert read("mfu.offline", tr) == pytest.approx(100 * flops / 0.1 / PEAK["flops"])


def test_readers_find_nothing_and_say_so():
    tr = synthetic()
    tr.ops, tr.modules = [], []
    for name in ("decode_step_ms.open", "prefill_ms_per_ktok.open", "attn_roofline.open",
                 "step_mfu.open"):
        assert read(name, tr) is None
