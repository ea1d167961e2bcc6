"""The engine's ``serve.*`` spans in a trace: the per-phase idle readers by
hand arithmetic, their sum against ``host_gap_ms.open``, idle gaps labelled
by engine phase, nothing read from a trace without engine spans, and a
tiny traced run of the whole harness."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import json  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
import types  # noqa: E402

import pytest  # noqa: E402

from bench import engine_spans, run, spec, trace  # noqa: E402
from bench.drive import Step  # noqa: E402
from bench.metrics_ctx import Context  # noqa: E402
from bench.trace import Event, Trace, _attribute, breakdown  # noqa: E402

MS = 1_000_000
MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
         "d_ff": 16, "vocab": 10}
PEAK = {"flops": 1e12, "hbm_bytes_per_s": 1e9}
PHASES = ("sched_gap_ms.open", "dispatch_gap_ms.open", "emit_gap_ms.open")
CHIP_TRACE = Path(__file__).parent / "chip_trace.xplane.pb"


def ev(a, b, name, **stats):
    return Event(int(a * MS), int(b * MS), name, stats)


def synthetic(tiled: bool):
    """A 100 ms window with two engine steps.  Step 0 (bench.step 5-35)
    runs a prefill chunk (8-11) while scheduling and a decode program
    (14-30); an eviction program (34-36) follows its emission.  Step 1
    (bench.step 55-75) decodes (60-70).  With ``tiled`` the engine's phases
    cover both ``bench.step`` spans; without, step 0's ``serve.step`` leaves
    5-6 out."""
    ops = [ev(8, 11, "%fusion.1 = bf16[1,512,960]{1,0} fusion(%p)"),
           ev(14, 30, "%copy.80 = bf16[1,1101,128,5,64]{2,4,3,1,0} copy(%x)"),
           ev(34, 36, "%scatter.3 = s32[64,32]{1,0} scatter(%t)"),
           ev(60, 70, "%copy.80 = bf16[1,1101,128,5,64]{2,4,3,1,0} copy(%x)")]
    mods = [ev(8, 11, "jit_chunk_fn"), ev(14, 30, "jit_decode_fn"),
            ev(34, 36, "jit_paged_set_row"), ev(60, 70, "jit_decode_fn")]
    _attribute(ops, mods)
    s0 = 5 if tiled else 6
    spans = [ev(0, 100, "bench.window"),
             ev(5, 35, "bench.step", step=0), ev(s0, 35, "serve.step", step=1),
             ev(s0, 12, "serve.schedule"), ev(7, 11, "serve.lane.chunk", rid=3, filled=512),
             ev(12, 31, "serve.decode", rows=2), ev(31, 35, "serve.emit", finished=1),
             ev(35, 55, "bench.idle"),
             ev(55, 75, "bench.step", step=1), ev(55, 75, "serve.step", step=2),
             ev(55, 58, "serve.schedule"), ev(58, 72, "serve.decode", rows=2),
             ev(72, 75, "serve.emit", finished=0)]
    return Trace(ops, mods, sorted(spans, key=lambda e: e.start), 1)


def context(tr):
    steps = [Step(0, 0.0, decode_lens=[3, 5], prompt_lens=[40]), Step(1, 0.0, decode_lens=[4, 6])]
    drv = types.SimpleNamespace(records={}, steps=steps)
    cell = types.SimpleNamespace(config={"model": MODEL})
    return Context(tr, drv, cell, 0.0, 1.0, PEAK)


def read(name, c):
    return spec.reader(name)(c)


def test_phase_readers_by_hand():
    c = context(synthetic(tiled=False))
    # step 0: schedule 6-12 less the chunk 8-11; decode 12-31 less 14-30;
    # emit 31-35 less 34-35.  Step 1: 3 ms; 14 less 60-70; 3 ms.
    assert read("sched_gap_ms.open", c) == pytest.approx((3 + 3) / 2)
    assert read("dispatch_gap_ms.open", c) == pytest.approx((3 + 4) / 2)
    assert read("emit_gap_ms.open", c) == pytest.approx((3 + 3) / 2)
    # bench.step 0 is idle 5-6 outside serve.step: half a millisecond a step
    phases = sum(read(n, c) for n in PHASES)
    assert read("host_gap_ms.open", c) == pytest.approx(phases + 0.5)


def test_phases_add_up_to_the_host_gap_when_they_tile_the_step():
    c = context(synthetic(tiled=True))
    assert read("sched_gap_ms.open", c) == pytest.approx((4 + 3) / 2)
    assert sum(read(n, c) for n in PHASES) == pytest.approx(read("host_gap_ms.open", c))


def test_breakdown_labels_a_gap_by_the_engine_phase_open_over_it():
    tr = synthetic(tiled=False)
    lo, hi = tr.window()
    labels = {round(s * 1e3): label for label, s in breakdown(tr, lo, hi)["idle_gaps"]}
    # idle 0-8, 11-14, 30-34, 36-60, 70-100: labelled at 4, 12.5, 32, 48, 85 ms
    assert labels == {8: "none", 3: "serve.decode", 4: "serve.emit", 24: "bench.idle",
                      30: "none"}


def test_readers_find_no_engine_spans_in_the_chip_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))   # no run's trace files here
    tr = trace.read(str(CHIP_TRACE))
    idx = sorted(int(s.stats["step"]) for s in tr.spans_named("bench.step"))
    steps = [Step(i, 0.0, decode_lens=[1000] * 64) for i in range(idx[-1] + 1)]
    c = Context(tr, types.SimpleNamespace(records={}, steps=steps),
                types.SimpleNamespace(config={"model": MODEL}), 0.0, 1.0, PEAK)
    n_spans = len(tr.spans)
    for name in PHASES:
        assert read(name, c) is None
    assert len(tr.spans) == n_spans
    assert read("host_gap_ms.open", c) == pytest.approx(6.530998)


def test_a_run_is_found_by_its_window(monkeypatch, tmp_path):
    """Spans read from a trace file under ``bench-trace-*`` are the run's only
    when its ``bench.window`` is the one the reader was handed."""
    import jax

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    out = tmp_path / "bench-trace-x"
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step", step=0):
            with jax.profiler.TraceAnnotation("serve.step", step=1):
                with jax.profiler.TraceAnnotation("serve.schedule"):
                    time.sleep(0.002)
                with jax.profiler.TraceAnnotation("serve.decode", rows=1):
                    time.sleep(0.003)
                with jax.profiler.TraceAnnotation("serve.emit", finished=0):
                    time.sleep(0.001)
    jax.profiler.stop_trace()
    tr = trace.read(str(out))
    assert not tr.spans_named("serve.step")
    steps = [Step(0, 0.0)]
    c = Context(tr, types.SimpleNamespace(records={}, steps=steps),
                types.SimpleNamespace(config={"model": MODEL}), 0.0, 1.0, PEAK)
    assert read("dispatch_gap_ms.open", c) >= 3.0
    assert [s.name for s in tr.spans] == ["bench.window", "bench.step", "serve.step",
                                          "serve.schedule", "serve.decode", "serve.emit"]
    assert tr.spans_named("serve.decode")[0].stats == {"rows": 1}
    other = Trace([], [], [ev(0, 1, "bench.window"), ev(0, 1, "bench.step", step=0)], 1)
    assert engine_spans.spans(context(other)) == []


def test_tiny_traced_run_splits_the_host_gap_by_phase():
    cfg = json.loads((Path(__file__).parent / "tiny.json").read_text())
    mix = {"kind": "poisson", "lead_s": 0.5,
           "prompt": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
           "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 30}}
    names = ["host_gap_ms.open", *PHASES]
    cell = spec.Cell("tiny.chat", 1, cfg, mix, {"rate_per_s": 4.0, "max_logit_gap": 0.012},
                     [], [{"name": n, "unit": "ms"} for n in names])
    res = run.serve(cell, 2**31 + 11, 2.0, True, t_start=time.perf_counter(),
                    device={"platform": "cpu"}, peak=PEAK)
    assert res["correct"]
    got = {n: res["metrics"][n]["value"] for n in names}
    # no device plane on the CPU, so every phase is idle from end to end
    # and only the call into Engine.step lies outside serve.step
    phases = sum(got[n] for n in PHASES)
    assert 0.8 * got["host_gap_ms.open"] <= phases <= got["host_gap_ms.open"] * (1 + 1e-9)
    labels = {label for label, _ in res["breakdown"]["idle_gaps"]}
    assert labels and all(lb.startswith(("serve.", "bench.")) or lb == "none" for lb in labels)
