"""The four-chip cell on the CPU at a tiny size: `sift4m.sharded.batch`
with a tiny sharded configuration, run by `harness.run` in a process that
sees four virtual CPU devices, comes out correct against the reference of
one index (the sharded store answers as one index)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from conftest import BENCH

SHARDS = 4


def test_sharded_cell_runs_and_is_correct(tiny_root):
    conf = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    conf.update(name="tiny-sharded", n_points=6000)
    conf["plan"]["backend"] = "sharded"
    (tiny_root / "bench/configs/tiny-sharded.json").write_text(
        json.dumps(conf))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-sharded", "source": "test",
                            "file": "bench/configs/tiny-sharded.json",
                            "reduced": [], "why": "CPU tests"})
    (cell,) = [w for w in spec["workloads"]
               if w["name"] == "sift4m.sharded.batch"]
    assert cell["chips"] == SHARDS
    cell["config"] = "tiny-sharded"
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    script = (
        "import json, sys, time\n"
        f"sys.path.insert(0, {str(tiny_root / 'bench')!r})\n"
        "import harness\n"
        "out = harness.run(harness.Path(sys.argv[1]), 'sift4m.sharded.batch',"
        " 5, 1, False, time.perf_counter(), require_tpu=False)\n"
        "print(json.dumps(out))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDS}")
    p = subprocess.run([sys.executable, "-c", script, str(tiny_root)],
                       cwd=BENCH.parent, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == SHARDS
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"qps", "setup_s"} <= set(out["metrics"])
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
