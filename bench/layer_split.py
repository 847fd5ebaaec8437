"""Where a cell's time goes, by the program's own spans, counters and
scopes: one process that builds the cell, runs an untraced window and then
a traced one, and prints one JSON line.

    python3 bench/layer_split.py --workload sift1m.batch --seed 7 --seconds 30

For each window: queries/s, and the serving queue's counters
(`DynamicBatcher.stats`) per batch, so the two side by side give the cost
of tracing.  For the traced window (the traffic's `trace_seconds`): device
busy time, `layer_seconds` and `idle_by_span` (`trace_layers.py`), with the
search program's scope map built from its compiled text at each batch
shape the traffic runs.  Needs a TPU, as `run.py` does; the harness's
traced run (`run.py --trace 1`) does not compute these yet.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_layers  # noqa: E402
import trace_reduce  # noqa: E402

PHASES = ("assemble", "dispatch", "sync", "resolve")


def queue_summary(rec: dict) -> dict:
    b = rec["batcher"]
    n = max(b["batches"], 1)
    return {
        "qps": len(rec["pool_rows"]) / (rec["t1"] - rec["t0"]),
        "batches": b["batches"], "requests": b["requests"],
        "queue_wait_mean_ms": b["wait_ns"] / max(b["requests"], 1) / 1e6,
        "ms_per_batch": {p: b[f"{p}_ns"] / n / 1e6
                         for p in ("batch",) + PHASES},
        "phases_over_batch": sum(b[f"{p}_ns"] for p in PHASES)
        / max(b["batch_ns"], 1),
    }


def search_program_texts(searcher, cell) -> list[str]:
    """Optimized HLO of the pallas search program at each chunk shape the
    traffic's batches run (the queue pads a batch to a power of two; the
    plan streams it in chunk_size rows)."""
    import jax
    import jax.numpy as jnp

    from repro.core import batched

    plan, t = searcher.plan, cell.traffic
    chunk = plan.chunk_size or t["max_batch"]
    rows = {min(1 << (n - 1).bit_length(), chunk) for n in
            range(t["request_rows"], t["max_batch"] + 1, t["request_rows"])}
    return [batched._search_impl.lower(
        searcher.index, searcher.cfg,
        jax.ShapeDtypeStruct((n, cell.config["dim"]), jnp.float32),
        cell.config["k"], "refined", plan.interpret,
        batched.get_candidate_pipeline("fused"), plan.d_chunk,
        plan.adaptive_r0).compile().as_text() for n in sorted(rows)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    args = ap.parse_args(argv)

    root = BENCH.parent
    sys.path.insert(0, str(root / "src"))
    try:
        out = split(root, args.workload, args.seed, args.seconds)
    except harness.RunError as e:
        print(f"layer_split: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


def split(root: Path, workload: str, seed: int, seconds: int,
          require_tpu: bool = True) -> dict:
    cell = harness.Cell.load(root, workload, trace=False)
    harness.check_device(cell, require_tpu)
    harness.enable_compile_cache(root)
    counter = harness.CompileCounter()
    batcher, pool, _ = harness.build(cell, seed)

    out = {"workload": workload, "seed": seed}
    rec = harness.window(cell, batcher, pool, seconds, seed, None, counter)
    out["untraced"] = queue_summary(rec)
    trace_dir = tempfile.mkdtemp(prefix="layer_split_")
    try:
        rec = harness.window(cell, batcher, pool,
                             cell.traffic["trace_seconds"], seed + 1,
                             trace_dir, counter)
        xplane = trace_reduce.find_xplane(trace_dir)
        tr = trace_reduce.reduce(trace_reduce.load(xplane))
        raw = trace_layers.load(xplane)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if tr is None:
        raise harness.RunError("the trace holds no window span, or no "
                               "device operation inside it")
    out["traced"] = queue_summary(rec)
    layers = trace_layers.module_layers(
        search_program_texts(batcher.searcher, cell))
    per_layer = trace_layers.layer_seconds(raw, layers)
    batches = max(rec["batcher"]["batches"], 1)
    out.update(
        busy_s=tr["busy_s"], window_s=tr["window_s"],
        layer_seconds=per_layer,
        layer_ms_per_batch={k: v / batches * 1e3
                            for k, v in per_layer.items()},
        idle_by_span=trace_layers.idle_by_span(raw))
    return out


if __name__ == "__main__":
    sys.exit(main())
