"""The engine's spans in the profiler's trace, its prefix counters, and the
program names the benchmark's trace readers match (CPU, smoke model)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # the checkout, for `bench`

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import engine_spans, trace  # noqa: E402
from repro.arch.model_zoo import build  # noqa: E402
from repro.configs.registry import get  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    Engine,
    KVConfig,
    Request,
    SchedulerConfig,
    ServeConfig,
)

BS, CHUNK, SLOTS, MAX_LEN = 8, 8, 2, 64
PHASES = ("serve.schedule", "serve.decode", "serve.emit")


@pytest.fixture(scope="module")
def smol():
    cfg = get("smollm-360m-smoke")
    return cfg, build(cfg).init(jax.random.PRNGKey(0))


def engine(smol, chunk=CHUNK):
    cfg, params = smol
    return Engine(cfg, params, ServeConfig(
        max_len=MAX_LEN,
        scheduler=SchedulerConfig(batch=SLOTS, prefill_chunk=chunk),
        kv=KVConfig(layout="paged", block_size=BS, prefix_sharing=True),
    ))


def prompts(vocab):
    """Two equal prompts of 2.5 blocks (the second aliases all of the
    first's, tail included, so its first decode copies the tail) and one
    unrelated prompt."""
    rng = np.random.default_rng(0)
    shared = rng.integers(0, vocab, 2 * BS + BS // 2).astype(np.int32)
    return [shared, shared.copy(), rng.integers(0, vocab, 13).astype(np.int32)]


def serve(eng, vocab):
    rids = [eng.submit(Request(p, max_new_tokens=6)) for p in prompts(vocab)]
    while eng.step():
        pass
    return rids


def traced_serve(eng, vocab, out):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the engine's spans need no Python call events
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        rids = serve(eng, vocab)
    finally:
        jax.profiler.stop_trace()
    return rids, engine_spans.read(str(out))


@pytest.fixture(scope="module")
def traced(smol, tmp_path_factory):
    out = tmp_path_factory.mktemp("serve-trace")
    eng = engine(smol)
    rids, spans = traced_serve(eng, smol[0].vocab, out)
    return eng, rids, spans, trace.read(str(out))


def within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_span_names(traced):
    _, _, spans, tr = traced
    names = {s.name for s in spans}
    assert names == {"serve.submit", "serve.step", *PHASES, "serve.lane.start",
                     "serve.lane.chunk", "serve.lane.install", "serve.cow"}
    assert tr.spans == []      # the harness's reader keeps only bench.* spans


def test_phases_tile_each_step_in_order(traced):
    _, _, spans, _ = traced
    steps = [s for s in spans if s.name == "serve.step"]
    assert [s.stats["step"] for s in steps] == list(range(1, len(steps) + 1))
    covered = total = 0
    for st in steps:
        kids = [s for s in spans if s.name in PHASES and within(s, st)]
        names = [s.name for s in kids]
        assert names in (list(PHASES), ["serve.schedule"]), names
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start
        covered += sum(s.end - s.start for s in kids)
        total += st.end - st.start
    assert covered >= 0.9 * total
    decode = [s for s in spans if s.name == "serve.decode"]
    assert all(1 <= s.stats["rows"] <= SLOTS for s in decode)
    assert sum(s.stats["finished"] for s in spans if s.name == "serve.emit") == 3


def test_admission_spans_sit_in_schedule_and_name_their_request(traced):
    eng, rids, spans, _ = traced
    schedules = [s for s in spans if s.name == "serve.schedule"]
    lane = [s for s in spans if s.name.startswith("serve.lane.") or s.name == "serve.cow"]
    assert all(any(within(s, sc) for sc in schedules) for s in lane)
    by = {n: [s for s in spans if s.name == n]
          for n in ("serve.lane.start", "serve.lane.chunk", "serve.lane.install")}
    assert [s.stats["rid"] for s in by["serve.lane.start"]] == rids
    assert [s.stats["hit_tokens"] for s in by["serve.lane.start"]] == [0, 2 * BS + BS // 2, 0]
    assert [s.stats["rid"] for s in by["serve.lane.install"]] == rids
    # the second prompt's blocks are all aliased: nothing of it is packed
    assert [s.stats["packed"] for s in by["serve.lane.install"]] == [3, 0, 2]
    lens = [len(p) for p in prompts(eng.cfg.vocab)]
    # each chunk names the prompt tokens in the scratch before and after it:
    # its own real tokens are [start, filled), the last chunk's padding not
    chunks = [(s.stats["rid"], s.stats["start"], s.stats["filled"])
              for s in by["serve.lane.chunk"]]
    assert chunks == [(r, f - CHUNK, min(f, n)) for r, n in zip(rids, lens)
                      for f in range(CHUNK, n + CHUNK, CHUNK)]
    real = [min(CHUNK, n - s) for n in lens for s in range(0, n, CHUNK)]
    assert [f - s for _, s, f in chunks] == real
    assert [s.stats["rid"] for s in spans if s.name == "serve.submit"] == rids
    assert [s.stats["n"] for s in spans if s.name == "serve.cow"] == [1]


def test_monolithic_admission_span_counts_its_requests(smol, tmp_path):
    eng = engine(smol, chunk=0)
    _, spans = traced_serve(eng, smol[0].vocab, tmp_path)
    admits = [s for s in spans if s.name == "serve.admit"]
    schedules = [s for s in spans if s.name == "serve.schedule"]
    assert all(any(within(a, sc) for sc in schedules) for a in admits)
    assert sum(s.stats["n"] for s in admits) == 3
    assert not any(s.name.startswith("serve.lane.") for s in spans)
    assert eng.stats["prefix_hit_tokens"] == 2 * BS + BS // 2


def test_prefix_counters_are_deterministic(smol, traced):
    eng = traced[0]
    assert eng.stats["prompt_tokens"] == sum(len(p) for p in prompts(smol[0].vocab))
    assert eng.stats["prefix_hit_tokens"] == 2 * BS + BS // 2
    again = engine(smol)
    serve(again, smol[0].vocab)
    assert again.stats == eng.stats
    assert all(type(v) is int for v in eng.stats.values())


def test_program_names_the_trace_readers_match(smol):
    """bench/metrics/ finds programs by these names in the trace's module
    events (``jit_<name>``): a rename would make a metric read nothing."""
    eng = engine(smol)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    key = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)  # noqa: E731
    scratch = jax.eval_shape(eng._fresh_scratch)
    n_blk = MAX_LEN // BS
    lowered = {
        "decode_fn": eng._decode.lower(eng.params, i32(SLOTS, 1), eng.caches,
                                       key(SLOTS, 2), i32(SLOTS)),
        "chunk_fn": eng._chunk_step.lower(eng.params, i32(1, CHUNK), scratch, i32(1), key(2)),
        "paged_store_row_blocks": eng.kv.pack_row.lower(
            eng.caches, {"k": scratch["k"], "v": scratch["v"]}, i32(), i32(), i32(2)),
        "paged_set_row": eng.kv.set_row.lower(eng.caches, i32(), i32(n_blk), i32()),
        "paged_copy_block": eng.kv.copy_block.lower(eng.caches, i32(), i32(), i32(), i32()),
        "_lambda": eng._fresh_scratch.lower(),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_decode_work_matches_the_device_work_list(smol, monkeypatch):
    """The ``work`` argument of ``serve.decode``, counted on the host from
    the engine's rows, equals the length of the paged kernel's work list
    that the decode program builds on the device, at every step, through
    evictions and slots waiting on the prefill lane, whose rows point at
    the sink and keep length 0; ``Engine.stats`` sums it beside the whole
    grid."""
    from repro.kernels.flash_attention import decode_attention
    from repro.serve import engine as engine_mod
    from repro.serve.kvcache import SINK_BLOCK

    paged_work_list = decode_attention.paged_work_list
    device, sink_lengths = [], []

    def recording(tables, lengths, bs):
        work = paged_work_list(tables, lengths, bs)
        jax.debug.callback(lambda n: device.append(int(n)), work[1])
        jax.debug.callback(
            lambda t, n: sink_lengths.extend(np.asarray(n)[np.asarray(t)[:, 0] == SINK_BLOCK]),
            tables, lengths,
        )
        return work

    host = []

    def span(name, **args):
        if name == "serve.decode":
            host.append(args["work"])
        return jax.profiler.TraceAnnotation(name, **args)

    monkeypatch.setattr(decode_attention, "paged_work_list", recording)
    monkeypatch.setattr(engine_mod, "_span", span)
    cfg, params = smol
    slots = 4
    eng = Engine(cfg, params, ServeConfig(
        max_len=MAX_LEN,
        scheduler=SchedulerConfig(batch=slots, prefill_chunk=CHUNK),
        kv=KVConfig(layout="paged", block_size=BS),
    ))
    rng = np.random.default_rng(1)
    for n, budget in [(13, 3), (20, 12), (5, 2), (30, 9), (9, 14), (17, 4)]:
        eng.submit(Request(
            rng.integers(0, cfg.vocab, n).astype(np.int32), max_new_tokens=budget
        ))
    while eng.step():
        pass
    jax.effects_barrier()

    assert len(host) > 10
    assert host == device
    # a row that holds no request attends over its one sink key, step
    # after step: its length never grows into blocks of its own
    assert sink_lengths and set(sink_lengths) == {1}
    assert eng.stats["decode_work_items"] == sum(host)
    assert eng.stats["decode_grid_full"] == len(host) * slots * (MAX_LEN // BS)
