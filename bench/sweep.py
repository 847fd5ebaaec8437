"""Find the knee of an open-loop cell: the highest offered rate at which the
queue's backlog does not grow across the window.

    python bench/sweep.py --workload sift1m.online --seed 5 --seconds 8 \\
        --rates 500,1000,2000,4000

Builds and warms the cell once, then offers each rate in turn (the cell's
traffic file with its `rate_per_s` replaced) in the same process.  Prints
one JSON line per rate: offered and completed rate, latency median and p99
(from the due time), and the mean backlog seen by arrivals in the first and
last quarter of the window.  Uses the TPU this process finds.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, default=8)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = Path(__file__).resolve().parent
    sys.path.insert(0, str(bench))
    import harness

    root = bench.parent
    cell = harness.Cell.load(root, args.workload, trace=False)
    sys.path.insert(0, str(root / "src"))
    try:
        harness.check_device(cell, require_tpu=True)
    except harness.RunError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(root)
    batcher, pool, _ = harness.build(cell, args.seed)
    print(f"[sweep] set-up {time.perf_counter() - T_START!r} s",
          file=sys.stderr, flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        rng = np.random.default_rng([args.seed, 10 + i])
        rec = cell.driver.run(batcher, pool, traffic, args.seconds, rng)
        lat = (rec["done"] - rec["due"]) * 1e3
        q = len(rec["backlog"]) // 4
        print(json.dumps({
            "offered_per_s": rate,
            "completed_per_s": len(rec["done"]) / (rec["t1"] - rec["t0"]),
            "window_s": rec["t1"] - rec["t0"],
            "latency_p50_ms": float(np.median(lat)),
            "latency_p99_ms": float(np.percentile(lat, 99)),
            "backlog_first_quarter": float(np.mean(rec["backlog"][:q])),
            "backlog_last_quarter": float(np.mean(rec["backlog"][-q:])),
            "requests": len(rec["done"]),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
