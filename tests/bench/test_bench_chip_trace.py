"""The trace reader and readers on a trace recorded on a TPU v5e: 0.48 s of
``smollm-360m.chat`` (three engine steps), from ``bench/run.py --trace 1
--trace-seconds 0.4 --keep-trace``.  That run printed the values these
tests expect, so the reduction here is the one the chip run made."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import types  # noqa: E402

import pytest  # noqa: E402

from bench import spec, trace  # noqa: E402
from bench.drive import Step  # noqa: E402
from bench.metrics_ctx import Context  # noqa: E402

TRACE = Path(__file__).parent / "chip_trace.xplane.pb"
MODEL = {"n_layers": 32, "d_model": 960, "n_heads": 15, "n_kv_heads": 5, "head_dim": 64,
         "d_ff": 2560, "vocab": 49152}
PEAK = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def tr():
    return trace.read(str(TRACE))


@pytest.fixture(scope="module")
def ctx(tr):
    idx = sorted(int(s.stats["step"]) for s in tr.spans_named("bench.step"))
    steps = [Step(i, 0.0) for i in range(idx[-1] + 1)]
    for i in idx:   # 64 rows of about a thousand live keys, as at that load
        steps[i].decode_lens = [1000] * 64
    drv = types.SimpleNamespace(records={}, steps=steps)
    cell = types.SimpleNamespace(config={"model": MODEL})
    return Context(tr, drv, cell, 0.0, 1.0, PEAK)


def test_chip_trace_structure(tr):
    lo, hi = tr.window()
    assert tr.n_devices == 1
    assert (hi - lo) / 1e9 == pytest.approx(0.480668454)
    assert trace.busy_ns(tr, lo, hi) / 1e9 == pytest.approx(0.460525009)
    assert len(tr.spans_named("bench.step")) == 3
    kernels = [o for o in tr.ops if trace.KERNEL in o.name]
    assert kernels and {o.module for o in kernels} == {"jit_decode_fn"}
    assert {m.name for m in tr.modules} >= {"jit_decode_fn", "jit_chunk_fn"}


def test_chip_trace_breakdown_names_the_pool_copies(tr):
    lo, hi = tr.window()
    b = trace.breakdown(tr, lo, hi)
    top = [k for k, _ in b["device_ops"]]
    assert top[0] == "jit_decode_fn/%closed_call.11 bf16[64,5,8,64]"
    assert any("copy" in k and "1101,128,5,64" in k for k in top)
    assert b["device_ops"][0][1] == pytest.approx(0.07143161300000002)
    assert len(b["idle_gaps"]) == 10 and b["idle_gaps"][0][0] == "bench.step"


@pytest.mark.parametrize("name,value", [
    ("host_gap_ms.open", 6.530998),
    ("decode_step_ms.open", 141.54059033333334),
    ("idle_share.open", 4.190714999574318),
])
def test_chip_trace_readers_reproduce_the_chip_run(ctx, name, value):
    assert spec.reader(name)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", ["attn_roofline.open", "step_mfu.open"])
def test_chip_trace_shares_are_shares(ctx, name):
    v = spec.reader(name)(ctx)
    assert 0 < v < 100
