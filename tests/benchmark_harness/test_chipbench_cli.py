"""The command itself: without a TPU, or in a directory that holds only
BENCHMARK.json and the files under ``paths``, it exits non-zero and prints
no result line."""

import os
import shutil
import subprocess
import sys

from chipbench_util import CHIP, ROOT


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"), "--workload", "train-1.5b-packed4k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _result_lines(stdout: str) -> list[str]:
    return [ln for ln in stdout.splitlines() if ln.startswith("{") and '"metrics"' in ln]


def test_command_without_a_tpu_exits_nonzero_and_prints_no_metric():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert _result_lines(r.stdout) == []
    assert "no accelerator" in r.stderr


def test_command_in_a_bare_checkout_fails(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program to run."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip", ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(str(tmp_path))
    assert r.returncode != 0 and _result_lines(r.stdout) == []
