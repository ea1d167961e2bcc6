"""chip_smoke.py off the chip: its phases at a tiny size on the CPU, and
its refusal to report a result without a TPU or without the repo."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _reports_ok(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except json.JSONDecodeError:
        return False


def test_smoke_fails_without_a_tpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert "[device] FAILED" in proc.stdout
    assert not _reports_ok(proc.stdout)


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert not _reports_ok(proc.stdout)


def test_smoke_phases_at_tiny_size(capsys):
    from repro.configs.registry import get

    chip_smoke.kernel_checks(
        0, slots=2, max_len=64, block=8, kv=2, g=2, d=16, d_model=32,
        widths=(48,),
    )
    with chip_smoke.CompileClock() as clock:
        chip_smoke.serve_checks(
            get("smollm-360m-smoke"), 0, clock,
            slots=3, max_len=64, block=8, chunk=16, bucket=16,
            n_requests=4, prompt_lens=(5, 40), new_tokens=4,
        )
    out = capsys.readouterr().out
    assert out.count("[kernel]") == 5 and "FAILED" not in out
    assert "4/4 FINISHED" in out and "fallbacks=0" in out


def test_smoke_checks_refuse_a_bad_run():
    with pytest.raises(chip_smoke.SmokeFailure, match="tolerance"):
        chip_smoke._report("k", [0.0, 1.0], [0.0, 1.5])
