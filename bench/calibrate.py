"""Readings for a cell's correctness limit: the program and the control.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 11 12 13

For each seed, in one process: a whole run of the cell (set-up, lead,
window, drain), then the check with the float8 control judged in the
program's place.  Prints one line per seed with the program's widest logit
gap (the lower reading), the control's (the upper reading) and the verdict
on the control, and exits non-zero off a TPU.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        device = run.device_info(cell.chips)
    except (run.Refused, KeyError, RuntimeError) as e:
        run.log(f"[refused] {e}")
        return 3
    run.enable_compile_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run.serve(cell, seed, args.seconds, False, t_start=t0, device=dict(device), control=True)
        line = {
            "seed": seed,
            "program_gap": res["program_gap"],
            "control_gap": res["compared"]["max_logit_gap"]["value"],
            "control_correct": res["correct"],
            "limit": res["compared"]["max_logit_gap"]["limit"],
            "attempted": res["attempted"],
            "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"].get("memory_peak_bytes"),
            "wall_s": time.perf_counter() - t0,
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
