"""The readings that the limits on `correct` are set from.

    python bench/readings.py --workload sift1m.batch --seconds 5 \\
        --seeds 11,12,13 [--control bfloat16]

For each seed, in one process: make the cell's data, build and warm it, run
a short window at the cell's own load through the same driver, and compare
the served answers with the plain reference (the program's readings).  With
`--control`, the reference computed in that lower precision is also put in
the program's place and compared (the control's readings, which must come
out as not correct).  Prints one JSON line per seed.  Uses the TPU this
process finds.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=5)
    ap.add_argument("--control", action="append", default=[])
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
        print(f"readings: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(root)
    counter = harness.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        batcher, pool, proj = harness.build(cell, seed)
        rec = harness.window(cell, batcher, pool, args.seconds, seed, None,
                             counter)
        del batcher
        gc.collect()
        nums = harness.reference_phase(cell, seed, proj, pool, rec, set(),
                                       tuple(args.control))
        print(json.dumps({"seed": seed, "program": nums,
                          "controls": rec["controls"],
                          "open_share": rec["open_share"],
                          "queries": len(rec["pool_rows"]),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
