"""A run of each cell on the CPU at a tiny size: the harness drives the
program through the queue, the reference agrees, and the look for a TPU
refuses a host without one."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from conftest import REPO


@pytest.mark.parametrize("workload", ["sift1m.batch", "sift1m.online"])
def test_cell_runs_and_is_correct(tiny_root, run_cell, workload):
    out = run_cell(tiny_root, workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_no_tpu_refused():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.batch",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_no_program_refused(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ gives no result."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.batch",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
