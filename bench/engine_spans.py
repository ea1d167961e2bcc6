"""The serve engine's own spans in a traced run, and the device-idle time
inside each of its step phases.

``repro.serve.engine`` annotates the profiler's trace with spans named
``serve.*`` (``serve.step``, tiled by ``serve.schedule``, ``serve.decode``
and ``serve.emit``; see the engine's module docstring).  They lie on the
same host thread and clock as the harness's ``bench.*`` spans, but
``bench/trace.read`` keeps only the latter.  So the first reader that asks
reads the engine's spans from the same ``.xplane.pb`` and adds them to the
``Trace`` it was handed, where ``trace.breakdown`` then labels each idle
gap with the innermost engine phase open over it.  That file is the one
``bench/run.py`` wrote under a fresh ``bench-trace-*`` directory of the
temporary directory, told from any other by its ``bench.window``.  A trace
with no engine spans (a program that has none) gives none, and every
reader here returns None.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import tempfile

from bench.trace import Event, _events, union

PREFIX = "serve."


def read(path: str, prefix: str | tuple[str, ...] = PREFIX) -> list[Event]:
    """Host events whose name starts with ``prefix`` in one ``.xplane.pb``,
    or in the only one under a directory, by start."""
    import jax

    if not path.endswith(".xplane.pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {path}: {found}")
        path = found[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [e for e in _events(line) if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e.start)


@functools.lru_cache(maxsize=1)
def _from_run(tmp: str, window: tuple[int, int]) -> tuple[Event, ...]:
    """The engine's spans from the trace file under ``tmp`` of the run
    whose traced window is ``window``; none if no such file is found."""
    pattern = os.path.join(tmp, "bench-trace-*", "**", "*.xplane.pb")
    for path in glob.glob(pattern, recursive=True):
        try:
            events = read(path, prefix=("bench.window", PREFIX))
        except RuntimeError:   # gone, or still being written by another run
            continue
        if any((e.start, e.end) == window for e in events if e.name == "bench.window"):
            return tuple(e for e in events if e.name.startswith(PREFIX))
    return ()


def spans(ctx) -> list[Event]:
    """The engine's spans of the traced run ``ctx`` reads, by start; added
    to ``ctx.trace.spans`` the first time they are read from the file."""
    tr = ctx.trace
    own = [s for s in tr.spans if s.name.startswith(PREFIX)]
    if own:
        return own
    found = list(_from_run(tempfile.gettempdir(), (ctx.lo, ctx.hi)))
    if found:
        tr.spans = sorted(tr.spans + found, key=lambda e: e.start)
    return found


def idle_ms_per_step(ctx, name: str) -> float | None:
    """Time inside the spans called ``name`` in which no device operation
    ran (averaged over chips, as ``host_gap_ms`` counts it), summed over
    the steps inside the trace and divided by their number; None if no
    such span lies in those steps."""
    steps = sorted(ctx.step_spans.values(), key=lambda sp: sp.start)
    starts = [sp.start for sp in steps]
    inside = []
    for sp in spans(ctx):
        i = bisect.bisect_right(starts, sp.start) - 1
        if sp.name == name and i >= 0 and sp.end <= steps[i].end:
            inside.append(sp)
    if not inside:
        return None
    ops = sorted((o.start, o.end) for o in ctx.trace.ops)
    op_starts = [s for s, _ in ops]
    longest = max((e - s for s, e in ops), default=0)
    n = max(1, ctx.trace.n_devices)
    idle = 0
    for sp in inside:
        near = ops[bisect.bisect_left(op_starts, sp.start - longest):
                   bisect.bisect_left(op_starts, sp.end)]
        idle += (sp.end - sp.start) - union(near, sp.start, sp.end) / n
    return idle / len(steps) / 1e6
