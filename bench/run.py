"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (the clock starts with the process): refuse anything but a TPU in
the peak table with enough chips; turn on JAX's compilation cache in the
checkout; make the weights on the device from the seed; build the engine
from the cell's configuration (paged KV, chunked prefill, prefix sharing,
no ABFT, no snapshots); serve warm-up requests that reach every program the
traffic can.  Then traffic starts, the window opens after the mix's lead
and stays open ``--seconds``; requests due in it are followed until their
first token.  After the window the device's memory peak is read, the
engine is freed, and a sample of the finished requests is checked against
the float32 reference.

The last line of standard output is one JSON object.  With ``--trace 0``
its metrics are the cell's end-to-end metrics; with ``--trace 1`` a
profiler trace of part of the window gives the per-layer metrics.  The
numbers compared for ``correct`` close both standard error and the line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import shutil  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TRACE_S = 4.0        # seconds of the window a traced run records
TRACE_AFTER_S = 1.0  # ... starting this long after the window opens


class Refused(RuntimeError):
    """The run cannot give a result here (no chip, unknown chip, ...)."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; refuse anything that
    is not a TPU in the peak table with at least ``chips`` chips."""
    import jax

    from bench import cost

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if info["platform"] != "tpu":
        raise Refused(f"no TPU: JAX found {info['platform']}")
    cost.peak(info["kind"])
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or ``$JAX_COMPILATION_CACHE_DIR``), keeping every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCount:
    """Compilations requested while ``on`` is set: backend compiles plus
    programs loaded from the persistent cache."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        self.traces = 0       # jaxprs traced (a retrace that hits no cache is also slow)
        self.gc_s = []        # garbage collections while on: seconds each
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _dur(self, event, secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
        if self.on and event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on:
            self.gc_s.append(time.perf_counter() - self._gc_t0)

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def check_params(params, mcfg) -> None:
    """The benchmark's weights must have the program's own layout."""
    import jax

    from repro.arch.model_zoo import build

    want = jax.eval_shape(build(mcfg).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
        (w.shape, w.dtype) != (g.shape, g.dtype)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got))
    ):
        raise RuntimeError("the served parameter layout differs from bench/weights.py")


def serve(cell, seed: int, seconds: float, trace: bool, t_start: float = T_START,
          device: dict | None = None, peak: dict | None = None,
          control: bool = False, trace_s: float = TRACE_S, keep_trace: str | None = None):
    """One run of ``cell``; returns the result object (without printing)."""
    import jax

    from bench import check, cost, drive, metrics_ctx, weights
    from bench import trace as trace_mod
    from bench.traffic import Traffic

    counter = CompileCount()
    mcfg, scfg = drive.engine_config(cell.config)
    params = weights.make(cell.config["model"], seed)
    check_params(params, mcfg)
    from repro.serve.engine import Engine

    eng = Engine(mcfg, params, scfg)
    stats0 = dict(eng.stats)
    traffic = Traffic(cell.traffic, cell.params, cell.config, seed, seconds)
    drv = drive.Runner(eng, traffic, cell.config, poll=trace)
    drv.warm_up(traffic.max_prompt)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tstate = {"ann": None, "stop": None}
    queue = {}

    def on_open():
        queue["open"] = drv.waiting
        counter.on = True
        if trace:
            tstate["start"] = time.perf_counter() + TRACE_AFTER_S

    def tick(now):
        if not trace:
            return
        if tstate["ann"] is None and not tstate.get("done") and "start" in tstate \
                and now >= tstate["start"]:
            jax.profiler.start_trace(tdir)
            tstate["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tstate["ann"].__enter__()
            tstate["stop"] = now + trace_s
        elif tstate["ann"] is not None and tstate["stop"] and now >= tstate["stop"]:
            _stop_trace(tstate)

    def on_close():
        queue["close"] = drv.waiting
        counter.on = False
        _stop_trace(tstate)

    opened, closed = drv.run(seconds, on_open=on_open, on_close=on_close, tick=tick)
    _stop_trace(tstate)
    stats = {k: v - stats0.get(k, 0) for k, v in eng.stats.items()}
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    if device is not None:
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    del eng, drv.eng
    gc.collect()

    e2e = metrics_ctx.end_to_end(drv, opened, closed)
    log(f"[samples] window requests {e2e['n_requests']}, first tokens {e2e['n_first']}, "
        f"gaps {e2e['n_gaps']}, tokens in window {e2e['n_tokens']}, "
        f"finished {len(drv.done)}")
    log(f"[e2e] {json.dumps(e2e['values'])}")
    if drv.late:
        late = sorted(drv.late)
        log(f"[generator] late ms p50 {1e3 * statistics.median(late):.3f} "
            f"p99 {1e3 * late[int(0.99 * (len(late) - 1))]:.3f} max {1e3 * late[-1]:.3f}")
    log(f"[queue] waiting at window open {queue.get('open')}, at close {queue.get('close')}")
    log(f"[engine] stats {json.dumps(stats)}")
    log(f"[compile] compilations inside the window: {counter.n} (expected 0); "
        f"traces {counter.traces}; garbage collections {len(counter.gc_s)}, "
        f"longest {1e3 * max(counter.gc_s, default=0.0):.1f} ms")
    slow = sorted(drv.steps, key=lambda st: st.t0 - st.t1)[:3]
    log("[steps] " + f"{len(drv.steps)} steps; slowest: " + ", ".join(
        f"#{st.index} {1e3 * (st.t1 - st.t0):.1f} ms at {st.t0 - opened:+.1f} s "
        f"({len(st.decode_lens)} decoded, prompts {st.prompt_lens})" for st in slow))

    verdict = check.check(params, cell.config, drv.done, cell.params, seed, control=control)
    log(f"[check] {verdict['checked_requests']} finished requests, "
        f"{verdict['checked_tokens']} served tokens against the reference")
    result = {
        "correct": bool(verdict["correct"]),
        "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "metrics": {},
        "device": device or {},
    }
    if trace:
        tr = trace_mod.read(tdir)
        pk = peak or cost.peak(dev.device_kind)
        ctx = metrics_ctx.Context(tr, drv, cell, opened, closed, pk)
        from bench import spec

        for m in cell.per_layer:
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window()
        result["device"]["busy_s"] = trace_mod.busy_ns(tr, lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = trace_mod.breakdown(tr, lo, hi)
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        values = {"setup_s": setup_s, **e2e["values"]}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    if control:
        result["program_gap"] = verdict["program_gap"]
    result["queue"] = queue
    result["compared"] = verdict["compared"]
    return result


def _stop_trace(tstate) -> None:
    import jax

    if tstate.get("ann") is not None:
        tstate["ann"].__exit__(None, None, None)
        tstate["ann"] = None
        tstate["done"] = True
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=TRACE_S,
                    help="length of the traced part of the window")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace here (to record a test fixture)")
    args = ap.parse_args(argv)
    from bench import spec

    cell = spec.load_cell(args.workload)
    try:
        device = device_info(cell.chips)
    except (Refused, KeyError, RuntimeError) as e:
        log(f"[refused] {e}")
        return 3
    enable_compile_cache()
    log(f"[device] {json.dumps(device)}")
    result = serve(cell, args.seed, args.seconds, bool(args.trace), device=device,
                   trace_s=args.trace_seconds, keep_trace=args.keep_trace)
    for name, c in result["compared"].items():
        log(f"[compared] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
