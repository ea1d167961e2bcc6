"""The harness as a whole: no result off a TPU, and on the CPU at tiny size
with the chip check skipped, ``correct`` holds for the program and fails
for the float8 control and for a token altered where it is produced."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))  # the checkout, for `bench`

import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

from bench import run, spec  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((Path(__file__).parent / "tiny.json").read_text())
LENGTHS = {"prompt": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
           "output": {"median": 8, "sigma": 0.8, "min": 2, "max": 30}}
MIXES = {
    "chat": {"kind": "poisson", "lead_s": 0.5, **LENGTHS},
    "offline": {"kind": "backlog", "lead_s": 0.5, "waiting_per_slot": 2, **LENGTHS},
    "sessions": {"kind": "sessions", "lead_s": 0.5, "system_prompts": 3, "system_len": 40,
                 "zipf": 1.1, "turns": [2, 4], "think_mean_s": 0.2,
                 "user": {"median": 8, "sigma": 0.8, "min": 2, "max": 20},
                 "answer": {"median": 6, "sigma": 0.8, "min": 2, "max": 12}},
}
LIMIT = 0.012  # the tiny model serves within ~0.001-0.005 of the reference; float8 ~0.05
PEAK = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


E2E = {
    "chat": ["ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms", "itl_mean_ms", "setup_s"],
    "offline": ["out_tok_s", "setup_s"],
    "sessions": ["ttft_p95_ms", "itl_mean_ms", "setup_s"],
}
HOST_SIDE = ["queue_wait_ms.open", "host_gap_ms.open", "prefix_hit_share.sessions"]


def tiny_cell(mix: str) -> spec.Cell:
    return spec.Cell(f"tiny.{mix}", 1, TINY, MIXES[mix],
                     {"rate_per_s": 4.0, "max_logit_gap": LIMIT},
                     [{"name": n, "unit": "x"} for n in E2E[mix]],
                     [{"name": n, "unit": "x"} for n in HOST_SIDE])


def serve(mix, seed, trace=False, **kw):
    return run.serve(tiny_cell(mix), seed, 2.0, trace, t_start=time.perf_counter(),
                     device={"platform": "cpu"}, peak=PEAK, **kw)


def no_result(proc) -> bool:
    return proc.returncode != 0 and not any(
        line.startswith("{") for line in proc.stdout.splitlines())


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "smollm-360m.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert no_result(p), p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in man["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, *man["command"][1:], "--workload", "smollm-360m.chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert no_result(p), p.stdout


@pytest.mark.parametrize("mix", ["chat", "offline", "sessions"])
def test_tiny_run_is_correct_and_reports_its_metrics(mix):
    res = serve(mix, 2**31 + 101)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    names = {m["name"] for m in tiny_cell(mix).end_to_end}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"


def test_tiny_traced_run_reports_host_side_layers():
    res = serve("sessions", 7, trace=True)
    assert res["correct"]
    for name in HOST_SIDE:
        assert name in res["metrics"], res["metrics"]
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_float8_control_is_not_correct():
    res = serve("chat", 2**31 + 202, control=True)
    assert not res["correct"], res["compared"]
    assert res["compared"]["max_logit_gap"]["value"] > LIMIT
    assert res["program_gap"] <= LIMIT, res["program_gap"]


def test_altered_token_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    argmax = jnp.argmax

    def shifted(x, axis=None, **kw):
        out = argmax(x, axis=axis, **kw)
        return (out + 1) % x.shape[-1]

    monkeypatch.setattr(jnp, "argmax", shifted)
    res = serve("chat", 2**31 + 303)
    assert not res["correct"], res["compared"]
