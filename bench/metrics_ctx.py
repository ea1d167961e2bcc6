"""End-to-end arithmetic, and what per-layer readers are handed.

Times come from the host clock at the token callback, which runs once the
step's tokens are back on the host.  A request belongs to the window when
it was due inside it; a gap between two tokens belongs to it when its later
token came inside it.
"""

from __future__ import annotations

import bisect
import math


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule (an observed value)."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def end_to_end(drv, opened: float, closed: float) -> dict:
    """End-to-end values of one run, and the counts behind them."""
    recs = list(drv.records.values())
    due_in = [r for r in recs if opened <= r.due < closed]
    ttft = [r.times[0] - r.due for r in due_in if r.times]
    gaps = []
    n_tokens = 0
    for r in recs:
        for a, b in zip(r.times, r.times[1:]):
            if opened <= b < closed:
                gaps.append(b - a)
        n_tokens += sum(1 for t in r.times if opened <= t < closed)
    offline = drv.traffic.kind == "backlog"
    values = {"out_tok_s": n_tokens / (closed - opened)}
    if not offline:
        if ttft:
            values["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
            values["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        if gaps:
            values["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
            values["itl_mean_ms"] = 1e3 * sum(gaps) / len(gaps)
    bad = {r.rid for r in drv.done if r.status != "FINISHED"}
    if offline:
        finished = [r for r in drv.done if r.times and opened <= r.times[-1] < closed]
        attempted = len(finished) + len([r for r in bad if r not in {f.rid for f in finished}])
        failed = len(bad)
    else:
        attempted = len(due_in)
        failed = sum(1 for r in due_in if not r.times or r.rid in bad)
    return {
        "values": values,
        "attempted": attempted,
        "failed": failed,
        "n_requests": len(due_in),
        "n_first": len(ttft),
        "n_gaps": len(gaps),
        "n_tokens": n_tokens,
    }


class Context:
    """What a per-layer reader gets: the trace and its window, the steps
    whose spans lie inside it, every request record, the model's sizes,
    the chip's peaks and the engine's counters."""

    def __init__(self, trace, drv, cell, opened: float, closed: float, peak: dict):
        self.trace = trace
        self.lo, self.hi = trace.window()
        self.model = cell.config["model"]
        self.peak = peak
        self.records = list(drv.records.values())
        self.opened, self.closed = opened, closed
        self.step_spans = {}
        for sp in trace.spans_named("bench.step"):
            if self.lo <= sp.start and sp.end <= self.hi:
                self.step_spans[int(sp.stats.get("step", -1))] = sp
        self.steps = [drv.steps[i] for i in sorted(self.step_spans) if 0 <= i < len(drv.steps)]
        spans = sorted(self.step_spans.values(), key=lambda sp: sp.start)
        self._starts = [sp.start for sp in spans]
        self._ends = [sp.end for sp in spans]

    def in_steps(self, events):
        """The events that start inside one of the steps in ``steps``."""
        out = []
        for e in events:
            i = bisect.bisect_right(self._starts, e.start) - 1
            if i >= 0 and e.start < self._ends[i]:
                out.append(e)
        return out

    def modules(self, *names: str):
        """Program executions in ``steps`` whose name holds any of ``names``."""
        return self.in_steps([m for m in self.trace.modules if any(n in m.name for n in names)])

    def ops(self, module: str, text: str):
        """Device operations in ``steps`` run by a program whose name holds
        ``module`` and whose own text holds ``text``."""
        return self.in_steps([o for o in self.trace.ops if module in o.module and text in o.name])
