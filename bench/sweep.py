"""Find an open-loop cell's knee: run it at several fixed rates in one process.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 2 4 8 16

For each rate, a whole run of the cell with that rate in place of the one
in ``bench/cells``, one line per rate: TTFT and gap tails, tokens/s, and
how many requests waited at the window's open and close.  A queue that
grows over the window means the rate is above what the system sustains.
The cell's file then takes 0.8 x the knee as a number; runs of the
benchmark never sweep.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        device = run.device_info(cell.chips)
    except (run.Refused, KeyError, RuntimeError) as e:
        run.log(f"[refused] {e}")
        return 3
    run.enable_compile_cache()
    names = ["ttft_p95_ms", "itl_p95_ms", "itl_mean_ms", "out_tok_s", "setup_s"]
    metrics = [{"name": n, "unit": ""} for n in names]
    for rate in args.rates:
        c = dataclasses.replace(cell, params=dict(cell.params, rate_per_s=rate), end_to_end=metrics)
        t0 = time.perf_counter()
        res = run.serve(c, args.seed, args.seconds, False, t_start=t0, device=dict(device))
        print(json.dumps({
            "rate_per_s": rate,
            **{k: v["value"] for k, v in res["metrics"].items()},
            "queue": res["queue"],
            "attempted": res["attempted"],
            "failed": res["failed"],
            "correct": res["correct"],
            "gap": res["compared"]["max_logit_gap"]["value"],
            "memory_peak_bytes": res["device"].get("memory_peak_bytes"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
