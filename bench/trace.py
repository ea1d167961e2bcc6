"""Read a JAX profiler trace into device intervals and the harness's spans.

``jax.profiler.ProfileData`` reads the ``.xplane.pb`` the profiler writes.
A TPU's plane is named ``/device:TPU:<n>``; its ``XLA Modules`` line holds
one event per program execution (``jit_decode_fn(<hash>)``), and its ``XLA
Ops`` line one event per operation that ran, named by the whole HLO
instruction (``%copy.80 = bf16[...] copy(...)``).  Operations carry no
program name, so each is given the program whose execution encloses it on
its chip.  A Pallas kernel shows as a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``.  The harness's own spans are
``TraceAnnotation`` events named ``bench.*`` on a host thread, with their
keyword arguments as stats.  Host and device events share the profiler's
clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import warnings

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = 'custom_call_target="tpu_custom_call"'
CONTROL_FLOW = (" while(", " conditional(", " call(")   # their time is their body's


@dataclasses.dataclass
class Event:
    start: int            # ns, profiler clock
    end: int
    name: str
    stats: dict
    module: str = ""      # device operations: the enclosing program's name

    @property
    def short(self) -> str:
        """An operation's name and result type, without its operands."""
        head = self.name.split("(", 1)[0] if " = " not in self.name else \
            self.name.split(" = ", 1)[0] + " " + self.name.split(" = ", 1)[1].split("{", 1)[0]
        return head.strip()


@dataclasses.dataclass
class Trace:
    ops: list[Event]          # device operations, all chips, by start
    modules: list[Event]      # device program executions, by start
    spans: list[Event]        # harness spans (bench.*), by start
    n_devices: int

    def window(self) -> tuple[int, int]:
        """The traced window: the ``bench.window`` span."""
        w = next(s for s in self.spans if s.name == "bench.window")
        return w.start, w.end

    def spans_named(self, name: str) -> list[Event]:
        return [s for s in self.spans if s.name == name]


def _events(line) -> list[Event]:
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)  # jaxlib's stats type
        for e in line.events:
            start = int(e.start_ns)
            out.append(Event(start, start + int(e.duration_ns), e.name, dict(e.stats)))
    return out


def read(path: str) -> Trace:
    """Read one ``.xplane.pb`` file, or the only one under a directory."""
    import jax

    if not path.endswith(".xplane.pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {path}: {found}")
        path = found[0]
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            lines = {line.name: _events(line) for line in plane.lines}
            mods = lines.get(MODULES_LINE, [])
            for m in mods:
                m.name = m.name.split("(", 1)[0]
            ops += _attribute(lines.get(OPS_LINE, []), mods)
            modules += mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e.name.startswith("bench.")]
    key = lambda e: e.start  # noqa: E731
    return Trace(sorted(ops, key=key), sorted(modules, key=key), sorted(spans, key=key), devices)


def _attribute(ops: list[Event], modules: list[Event]) -> list[Event]:
    """Name each operation of one chip after the program execution that
    encloses its start."""
    starts = [m.start for m in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < modules[i].end:
            o.module = modules[i].name
    return ops


def union(intervals: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to ``[lo, hi)``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: Trace, lo: int, hi: int) -> float:
    """Time in ``[lo, hi)`` in which an operation ran, averaged over chips."""
    return union([(o.start, o.end) for o in tr.ops], lo, hi) / max(1, tr.n_devices)


def gaps(tr: Trace, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of the device inside ``[lo, hi)``."""
    out, t = [], lo
    for s, e in sorted((o.start, o.end) for o in tr.ops):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def breakdown(tr: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle gaps
    labelled by the harness span open over them."""
    by_op: dict[str, float] = {}
    for o in tr.ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s and not any(c in o.name for c in CONTROL_FLOW):
            key = f"{o.module}/{o.short}"
            by_op[key] = by_op.get(key, 0.0) + (e - s) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    starts = np.asarray([s.start for s in tr.spans])
    for a, b in sorted(gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:top]:
        label = "none"
        mid = (a + b) // 2
        for i in range(np.searchsorted(starts, mid, side="right") - 1, -1, -1):
            sp = tr.spans[i]
            if sp.name != "bench.window" and sp.start <= mid < sp.end:
                label = sp.name
                break
        idle.append([label, (b - a) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}
