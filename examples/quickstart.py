"""Quickstart: the paper's active search, end to end, in ~40 lines.

  PYTHONPATH=src python examples/quickstart.py

Reproduces the paper's workflow (Figs. 1-2): rasterize 2-D points onto an
image, actively search a query's neighbors by adapting the radius (Eq. 1),
and classify by per-class counts — then sanity-check against exact kNN.

ONE handle serves every execution path: `ActiveSearcher` bundles the index
with an `ExecutionPlan` (backend, interpret, chunk_size), and `.with_plan()`
re-plans the same index onto another registered backend.
"""

import numpy as np
import jax.numpy as jnp

from repro.api import ActiveSearcher, ExecutionPlan, GridConfig, identity_projection

rng = np.random.default_rng(0)

# --- the data set: N 2-D points with 3 classes (paper §3) -------------------
N, K = 50_000, 11
points = jnp.asarray(rng.normal(size=(N, 2)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 3, size=N), jnp.int32)

# --- build the "image": grid + per-class count pyramid + CSR buckets --------
cfg = GridConfig(
    grid_size=1024,   # the image resolution (paper used 3000x3000)
    n_classes=3,      # one count channel per class (paper §2)
    r0=16,            # initial radius, pixels (paper used 100)
    window=64,        # candidate gather window (cells)
    row_cap=64,
    k_slack=2.0,      # accept n in [k, 2k] then re-rank (production mode)
)
searcher = ActiveSearcher.build(
    points, labels=labels, cfg=cfg, proj=identity_projection(points)
)
print("index stats      :", {k_: v for k_, v in searcher.stats().items()
                             if k_ in ("n_points", "levels", "backend")})

# --- search: zoom around the query, not over the dataset --------------------
queries = jnp.asarray(rng.normal(size=(5, 2)), jnp.float32)
res = searcher.search(queries, K)             # batched active search (jnp plan)
print("neighbor ids[0]  :", np.asarray(res.ids[0]))
print("distances[0]     :", np.round(np.asarray(res.dists[0]), 4))
print("Eq.1 radius/iters:", np.asarray(res.radius), np.asarray(res.iters))

# --- same index, kernel-backed plan -----------------------------------------
# backend="pallas" runs the Eq.-1 loop on the level-scheduled
# kernels.tile_count_multilevel (one pallas_call per iteration counts every
# query from its own pyramid level), then ranks candidates with the FUSED
# kernels.csr_candidate_topk: window spans are scalar-prefetched and
# candidate rows stream straight from the CSR store into VMEM, so no
# (B, window*row_cap) intermediate is ever materialized (interpreted on the
# CPU backend, compiled to Mosaic on a TPU).  Results
# are identical to the jnp plan; chunk_size= streams big batches through
# fixed-shape kernel invocations without changing any result.
res_k = searcher.with_plan(backend="pallas").search(queries, K)
assert np.array_equal(np.asarray(res.ids), np.asarray(res_k.ids))
assert np.array_equal(np.asarray(res.dists), np.asarray(res_k.dists))
print("pallas plan      : identical ids/dists ✓")

# --- classify like the paper's Fig. 2 (argmax of per-class circle counts) ---
pred_paper = searcher.classify(queries, K, mode="paper")
pred_refined = searcher.classify(queries, K, mode="refined")
truth = searcher.with_plan(backend="exact").classify(queries, K)
print("paper-mode predictions :", np.asarray(pred_paper))
print("refined predictions    :", np.asarray(pred_refined))
print("exact kNN ground truth :", np.asarray(truth))

# --- the paper's property: query cost independent of N ----------------------
import time
plan = ExecutionPlan(backend="jnp")
for n in (10_000, 100_000, 1_000_000):
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    s_n = ActiveSearcher.build(pts, cfg=cfg, plan=plan,
                               proj=identity_projection(pts))
    s_n.search(queries, K).ids.block_until_ready()   # warm
    t0 = time.perf_counter()
    s_n.search(queries, K).ids.block_until_ready()
    print(f"N={n:>9,}: active search {1e3*(time.perf_counter()-t0):6.1f} ms")
