"""Fixtures for the benchmark's own tests, which run on the CPU at tiny
sizes: `python -m pytest bench/tests`.

`tiny_root` is a checkout-like directory: a copy of `bench/`, the program's
`src/` linked in, and a BENCHMARK.json whose cells run a tiny configuration
and tiny traffic mixes, written as new files beside the real ones.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))


def tiny_spec(root: Path) -> dict:
    """Write the tiny configuration and traffic files; return the spec."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "sift1m-l2.json").read_text())
    conf.update(name="tiny", n_points=4000, dim=16, n_queries=64,
                check_sample=64)
    conf["plan"]["chunk_size"] = 8
    conf["grid"].update(grid_size=64, window=8, row_cap=8, r0=4)
    conf["generator"].update(n_clusters=16)
    (root / "bench/configs/tiny.json").write_text(json.dumps(conf))
    for mix, over in (("batch", {"request_rows": 16, "max_batch": 16}),
                      ("online", {"rate_per_s": 50, "max_batch": 4})):
        tr = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
        tr.update(over, trace_seconds=1)
        (root / f"bench/traffic/tiny_{mix}.json").write_text(json.dumps(tr))
    spec["configs"] = [{"name": "tiny", "source": "tiny test size",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "CPU tests"}]
    for w in spec["workloads"]:
        w["config"] = "tiny"
        w["traffic"] = "tiny_" + w["traffic"]
    return spec


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(REPO / "src", root / "src")
    spec = tiny_spec(root)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def run_cell(monkeypatch):
    """harness.run on the CPU (the look for a TPU skipped)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    import harness

    def run(root, workload, seed=3, seconds=1, trace=False):
        return harness.run(root, workload, seed, seconds, trace,
                           time.perf_counter(), require_tpu=False)

    return run
