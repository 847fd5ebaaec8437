"""A configuration, a traffic mix and a metric added as new files (and
BENCHMARK.json entries) are found by name and run, with no existing file
of the harness edited."""

from __future__ import annotations

import hashlib
import json


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found(tiny_root, run_cell):
    before = _digest(tiny_root)
    conf = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    conf.update(name="tiny-wide", dim=32, n_points=3000)
    (tiny_root / "bench/configs/tiny-wide.json").write_text(json.dumps(conf))
    (tiny_root / "bench/traffic/small_closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "request_rows": 8, "max_batch": 8,
         "trace_seconds": 1}))
    (tiny_root / "bench/metrics/served_rows_per_request.py").write_text(
        "def read(rec):\n"
        "    return len(rec['pool_rows']) / len(rec['request_rows'])\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "bench/configs/tiny-wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "wide.small", "config": "tiny-wide",
                              "traffic": "small_closed", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "served_rows_per_request",
                               "unit": "rows", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": ["wide.small"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    out = run_cell(tiny_root, "wide.small")
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["served_rows_per_request"]["value"] == 8.0
    assert "setup_s" in out["metrics"]
    after = _digest(tiny_root)
    assert {k: v for k, v in after.items() if k in before} == before
