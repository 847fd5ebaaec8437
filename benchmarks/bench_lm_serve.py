"""LM-side serving benchmark: decode throughput with and without the
active-search kNN-LM head, plus the dynamic-batching queue under a
closed-loop decode-stream workload (smoke-scale model on CPU — the
datastore search cost is the quantity of interest; the LM is constant
between the rows).

The queue workload replays the engine's OWN decode stream through
`launch.serve.DynamicBatcher`: every decode step's hidden batch arrives as
a ragged search request (1..B rows), every few steps the (hidden ->
next-token) pairs are offered to the insert backlog, and the queue serves
closed-loop — one dynamic batch at a time, draining inserts between
batches.  That is exactly the `--knn-online` serving loop, so the recorded
p50/p99 latency, qps, backlog depth, and compaction pauses are the serving
tier's, not a synthetic microbenchmark's.

Results land in BENCH_serve.json (see REPRO_BENCH_ARTIFACTS) so CI records
the serving-tier trajectory next to BENCH_mutation.json; the
`parity_queue_vs_direct` field is a drift gate (render_bench_table.py
--check fails on False).

Env knobs:
  REPRO_BENCH_QUICK=1      smallest datastore only, shorter decode stream
  REPRO_BENCH_ARTIFACTS=D  directory for BENCH_serve.json (default ".")
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv
from repro import api
from repro.configs import get_smoke
from repro.core import knn_lm
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import (
    DynamicBatcher,
    Engine,
    ServeConfig,
    build_datastore_from_model,
)
from repro.models import model as M


def _quick() -> bool:
    return os.environ.get("REPRO_BENCH_QUICK", "0") == "1"


def _queue_workload(store, knn_cfg, hiddens, tokens) -> dict:
    """Closed-loop decode-stream workload through the DynamicBatcher.

    hiddens: per-step (B, d) arrays from Engine.generate; tokens: (B, new).
    Returns the queue metrics dict for BENCH_serve.json."""
    searcher = api.ActiveSearcher.from_index(store, knn_cfg.grid)
    q = DynamicBatcher(searcher, k=knn_cfg.k, max_batch=64)

    # parity gate BEFORE any inserts: queue-padded results must be
    # bit-identical to a direct unpadded search on the same handle
    probe = jnp.asarray(np.asarray(hiddens[0][:3], np.float32))
    fut = q.submit(probe)
    q.drain()
    got, want = fut.result(timeout=0), searcher.search(probe, knn_cfg.k)
    parity = all(
        np.array_equal(np.asarray(getattr(got, f)), np.asarray(getattr(want, f)))
        for f in want._fields
    )

    rng = np.random.default_rng(0)
    b = hiddens[0].shape[0]
    # warm the pow2 shape ladder the batcher pads to, so the timed loop
    # measures serving (incl. insert drains), not jit compilation
    h0 = np.asarray(hiddens[0], np.float32)
    for w in (1, 2, 4, 8, 16):
        warm = np.repeat(h0[:1], w, axis=0)
        jax.block_until_ready(searcher.search(jnp.asarray(warm), knn_cfg.k).ids)
    # warm the insert+snapshot path on a throwaway handle (same shapes the
    # drain will hit); the timed loop then pays real insert cost, not traces
    throwaway = searcher.insert(
        jnp.asarray(h0), labels=jnp.zeros((h0.shape[0],), jnp.int32))
    jax.block_until_ready(throwaway.index.points_sorted)

    # each request timed on its own clock, from submit to its answer
    latencies: list[float] = []

    def submit(rows):
        t_submit = time.perf_counter()
        q.submit(rows).add_done_callback(
            lambda _: latencies.append(time.perf_counter() - t_submit))

    t0 = time.perf_counter()
    for step, h in enumerate(hiddens):
        h = np.asarray(h, np.float32)
        # ragged arrivals: a random non-empty prefix of the decode batch
        rows = int(rng.integers(1, b + 1))
        submit(h[:rows])
        if step % 4 == 3:  # periodic online growth from the decode stream
            vals = jnp.asarray(tokens[:, step + 1], jnp.int32)
            q.offer_insert(jnp.asarray(h), labels=vals)
        q.step()  # closed loop: serve as requests arrive
    q.drain()
    jax.block_until_ready(q.searcher.index.points_sorted)
    wall_s = time.perf_counter() - t0

    lat = np.asarray(latencies, np.float64)
    st = q.searcher.stats()
    return {
        "requests": q.stats["requests"],
        "request_rows": q.stats["request_rows"],
        "batches": q.stats["batches"],
        "mean_batch_rows": q.stats["batch_rows"] / max(q.stats["batches"], 1),
        "pad_rows": q.stats["pad_rows"],
        "pad_frac": q.stats["pad_rows"]
        / max(q.stats["batch_rows"] + q.stats["pad_rows"], 1),
        "p50_latency_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_latency_ms": float(np.percentile(lat, 99) * 1e3),
        "qps": q.stats["request_rows"] / wall_s,
        "insert_rows_queued": q.stats["insert_rows_queued"],
        "insert_backlog_peak": q.stats["insert_backlog_peak"],
        "inserts_applied": q.stats["inserts_applied"],
        "compactions": st.get("compactions", 0),
        "compact_pause_s": st.get("compact_s", 0.0),
        "parity_queue_vs_direct": bool(parity),
    }


def main(datastore_sizes=None) -> None:
    quick = _quick()
    if datastore_sizes is None:
        datastore_sizes = (4096,) if quick else (4096, 65_536)
    max_new = 8 if quick else 16
    cfg = get_smoke("internlm2-1.8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, size=(8, 32), dtype=np.int32)
    csv = Csv("mode,datastore_n,decode_tok_per_s")

    engine = Engine(cfg, params, mesh, ServeConfig(max_new_tokens=max_new))
    engine.generate(prompts)  # warm
    engine.stats = {"prefill_s": 0, "decode_s": 0, "tokens": 0}
    engine.generate(prompts)
    lm_only = engine.stats["tokens"] / engine.stats["decode_s"]
    csv.row("lm_only", 0, f"{lm_only:.1f}")

    knn_cfg = knn_lm.KNNLMConfig(k=8)
    decode_rows = []
    store = hiddens = toks = None
    for n in datastore_sizes:
        corpus = rng.integers(0, cfg.vocab_size, size=(n // 64, 65), dtype=np.int32)
        store = build_datastore_from_model(cfg, params, corpus, knn_cfg)
        eng = Engine(cfg, params, mesh, ServeConfig(max_new_tokens=max_new, knn=knn_cfg),
                     datastore=store)
        eng.generate(prompts)  # warm
        eng.stats = {"prefill_s": 0, "decode_s": 0, "tokens": 0}
        toks, hiddens = eng.generate(prompts)
        tps = eng.stats["tokens"] / eng.stats["decode_s"]
        csv.row("knn_lm_active_search", store.n_points, f"{tps:.1f}")
        decode_rows.append({"datastore_n": int(store.n_points),
                            "knn_tok_per_s": tps})

    queue = _queue_workload(store, knn_cfg, hiddens, toks)
    csv.row("queue_p50_latency_ms", store.n_points,
            f"{queue['p50_latency_ms']:.2f}")
    csv.row("queue_p99_latency_ms", store.n_points,
            f"{queue['p99_latency_ms']:.2f}")
    csv.row("queue_qps", store.n_points, f"{queue['qps']:.1f}")
    csv.row("queue_insert_backlog_peak", store.n_points,
            queue["insert_backlog_peak"])
    csv.row("queue_parity_vs_direct", store.n_points,
            queue["parity_queue_vs_direct"])

    results = {
        "schema": 1, "timestamp": time.time(), "quick": quick,
        "decode": {"lm_only_tok_per_s": lm_only, "rows": decode_rows},
        "queue": queue,
    }
    art_dir = os.environ.get("REPRO_BENCH_ARTIFACTS", ".")
    path = os.path.join(art_dir, "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[bench_lm_serve] wrote {path}", flush=True)
    return csv


if __name__ == "__main__":
    main()
