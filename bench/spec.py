"""Find every part of one cell by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
The harness reads, all under ``bench/``:

* ``configs/<config>.json``   model sizes, serve settings, source, cuts;
* ``traffic/<traffic>.json``  the mix's parameters for ``traffic.py``;
* ``cells/<cell>.json``       what the cell fixes beyond them (a rate);
* ``metrics/<metric>.py``     one reader per per-layer metric, found by the
  metric's full name or, failing that, by its name up to the first dot.

Nothing here names a cell, so a cell, a mix or a metric is added by adding
files and entries, never by editing code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    params: dict          # bench/cells/<cell>.json
    end_to_end: list      # manifest metric entries this cell reports
    per_layer: list


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, manifest: Path = MANIFEST) -> Cell:
    """The cell called ``name`` with its files; KeyError if it is unknown."""
    man = _load(manifest)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=_load(BENCH / "traffic" / f"{entry['traffic']}.json"),
        params=_load(BENCH / "cells" / f"{name}.json"),
        end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in man["per_layer"] if _applies(m, name)],
    )


def reader(metric: str):
    """The ``read`` function of a per-layer metric's reader module."""
    base = metric.split(".", 1)[0]
    for stem in (metric, base):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {metric!r} in bench/metrics")
