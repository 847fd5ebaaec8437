"""Run one benchmark cell on the TPU this process finds.

    python bench/run.py --workload sift1m.batch --seed 7 --seconds 20 --trace 0

Prints progress and the compared numbers on standard error, and as the last
line of standard output one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its per-layer
ones), `device`, with `--trace 1` a `breakdown`, and last `checks`, each
compared number beside its limit.  Exits non-zero, with no result, where JAX
finds no TPU or fewer chips than the cell asks for.  See harness.py.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = Path(__file__).resolve().parent
    sys.path.insert(0, str(bench))
    import harness

    try:
        out = harness.run(bench.parent, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
