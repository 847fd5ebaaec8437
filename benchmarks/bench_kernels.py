"""Kernel microbench: the pure-JAX reference paths (what actually executes on
CPU) timed across sizes, plus one interpret-mode validation per Pallas kernel
(interpret=True timings are NOT hardware-meaningful — correctness only).

The stacked-vs-level-scheduled counting comparison IS meaningful on CPU
interpret: both paths pay the same per-program emulation cost, so the ratio
reflects the kernel-invocation count (L stacked passes vs one scheduled
pass).  Results land in BENCH_kernels.json (see REPRO_BENCH_ARTIFACTS) so CI
records the perf trajectory.

Env knobs:
  REPRO_BENCH_QUICK=1      shrink sweeps to CI-friendly sizes
  REPRO_BENCH_ARTIFACTS=D  directory for BENCH_kernels.json (default ".")
"""

from __future__ import annotations

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, timeit
from repro.kernels import ops, ref


def _quick() -> bool:
    return os.environ.get("REPRO_BENCH_QUICK", "0") == "1"


def main() -> None:
    rng = np.random.default_rng(0)
    csv = Csv("kernel,config,ref_us_per_call,pallas_interpret_ok")
    results: dict = {"schema": 1, "timestamp": time.time(), "quick": _quick()}

    # tile_count: one pyramid-level circle count
    sizes = ((256, 16, 1),) if _quick() else ((256, 16, 1), (1024, 16, 4))
    for s, tile, c in sizes:
        level = jnp.asarray(rng.integers(0, 4, size=(s, s, c)), jnp.int32)
        q = jnp.asarray(rng.uniform(0, s, size=(64, 2)), jnp.float32)
        r = jnp.asarray(rng.uniform(1, tile / 2 - 1.5, size=(64,)), jnp.float32)
        t = timeit(lambda: ref.tile_count(level, q, r, 1, tile), repeats=5)
        ok = bool(np.array_equal(
            np.asarray(ops.tile_count(level, q, r, 1, tile, interpret=True)),
            np.asarray(ref.tile_count(level, q, r, 1, tile)),
        ))
        csv.row("tile_count", f"S={s} T={tile} C={c} B=64", f"{t*1e6/64:.1f}", ok)

    # candidate_topk: post-gather re-rank
    shapes = ((64, 256, 64, 16),) if _quick() else \
        ((64, 256, 64, 16), (256, 1024, 128, 16))
    for b, c, d, k in shapes:
        cand = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
        valid = jnp.asarray(rng.uniform(size=(b, c)) > 0.2)
        q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
        t = timeit(lambda: ref.candidate_topk(cand, valid, q, k), repeats=5)
        gd, _ = ops.candidate_topk(cand[:4], valid[:4], q[:4], k, interpret=True)
        wd, _ = ref.candidate_topk(cand[:4], valid[:4], q[:4], k)
        ok = bool(np.allclose(np.asarray(gd), np.asarray(wd), atol=1e-4))
        csv.row("candidate_topk", f"B={b} C={c} d={d} k={k}", f"{t*1e6/b:.1f}", ok)

    # brute_knn: the paper's baseline
    brute = ((100, 10_000, 2, 11),) if _quick() else \
        ((100, 10_000, 2, 11), (100, 100_000, 2, 11))
    for b, n, d, k in brute:
        q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        t = timeit(lambda: ref.brute_knn(q, x, k), repeats=3)
        gd, _ = ops.brute_knn(q[:4], x[:2048], k, interpret=True)
        wd, _ = ref.brute_knn(q[:4], x[:2048], k)
        ok = bool(np.allclose(np.asarray(gd), np.asarray(wd), atol=1e-4))
        csv.row("brute_knn", f"B={b} N={n} d={d} k={k}", f"{t*1e6/b:.1f}", ok)

    results["count_paths"] = bench_count_paths(rng, csv)
    results["candidate_paths"] = bench_candidate_paths(rng, csv)
    if not _quick():
        results["search_backends"] = bench_search_backends(rng, csv)

    art_dir = os.environ.get("REPRO_BENCH_ARTIFACTS", ".")
    path = os.path.join(art_dir, "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[bench_kernels] wrote {path}", flush=True)
    return csv


def bench_count_paths(rng, csv: Csv) -> dict:
    """Stacked (L x tile_count + select) vs level-scheduled
    (tile_count_multilevel) counting — the Eq.-1 loop body.

    Config note: the CPU interpreter charges every grid program a copy of
    every operand (the operands ride in its while_loop carry), a cost real
    hardware does not pay — on TPU the index_map DMAs only the addressed
    (T, T, C) blocks.  A VMEM-scale pyramid keeps that artifact small, so
    the ratio below reflects what the scheduler actually removes: L
    pallas_calls-worth of programs per Eq.-1 iteration vs one.

    Both count paths run through the facade: the stacked baseline is the
    registered count-only backend "pallas_stacked"."""
    from repro.api import ActiveSearcher, ExecutionPlan, GridConfig, identity_projection

    # same config in quick mode: smaller sweeps time too few programs to
    # measure reliably, and this one still finishes in seconds
    b, grid, tile = 128, 128, 8
    cfg = GridConfig(grid_size=grid, tile=tile, window=32,
                     row_cap=32, r0=10, k_slack=2.0)
    n = 5_000
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    multi = ActiveSearcher.build(
        pts, cfg=cfg, proj=identity_projection(pts),
        plan=ExecutionPlan(backend="pallas", interpret=True),
    )
    stacked = multi.with_plan(backend="pallas_stacked")
    q = jnp.asarray(rng.normal(size=(b, 2)), jnp.float32)
    radii = jnp.asarray(rng.integers(1, cfg.max_radius, size=b), jnp.int32)

    # one pass is only ~5-15 ms, so generous repeats keep the median stable
    # against scheduler noise at negligible cost
    t_stack = timeit(lambda: stacked.count_at(q, radii), repeats=25, warmup=3)
    t_multi = timeit(lambda: multi.count_at(q, radii), repeats=25, warmup=3)
    parity = bool(np.array_equal(
        np.asarray(multi.count_at(q, radii)),
        np.asarray(stacked.count_at(q, radii)),
    ))
    out = {
        "levels": cfg.levels,
        "batch": b,
        "grid_size": grid,
        "tile": tile,
        "stacked_counts_per_s": b / t_stack,
        "level_scheduled_counts_per_s": b / t_multi,
        "speedup": t_stack / t_multi,
        "parity": parity,
    }
    csv.row("counts_stacked", f"L={cfg.levels} B={b} G={grid} T={tile}",
            f"{t_stack*1e6/b:.1f}", parity)
    csv.row("counts_level_scheduled", f"L={cfg.levels} B={b} G={grid} T={tile}",
            f"{t_multi*1e6/b:.1f}", parity)
    print(f"[bench_kernels] level scheduler speedup over stacked "
          f"(L={cfg.levels}): {out['speedup']:.2f}x", flush=True)
    return out


def bench_candidate_paths(rng, csv: Csv) -> dict:
    """Fused csr_candidate_topk vs the gather pipeline (one-shot window
    gather + dense candidate_topk) — the candidate stage in isolation.

    The CPU interpreter emulates the fused kernel's per-row DMAs element by
    element, so the interpret-mode RATIO is not hardware-meaningful (unlike
    count_paths) — run this sweep compiled (interpret=False) on a TPU to
    read the real speedup.  What IS meaningful everywhere: the recorded
    bit-parity of (dists, global indices) between the two paths, and the
    candidate-stage HBM intermediate each needs — the gather path
    materializes (B, w*row_cap) x four record fields; the fused path writes
    only the (B, k) result pair."""
    n, d, b, w, rcap, k = (10_000, 8, 8, 16, 16, 8) if _quick() else \
        (100_000, 16, 32, 32, 32, 16)
    store = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, n - rcap, size=(b, w)), jnp.int32)
    ends = jnp.minimum(
        starts + jnp.asarray(rng.integers(0, rcap + 4, size=(b, w)), jnp.int32),
        n,
    )

    def fused():
        return ops.csr_candidate_topk(
            store, starts, ends, q, k, n, rcap, interpret=True
        )

    def gather():
        s_cl = jnp.clip(starts, 0, n - rcap)
        j = s_cl[:, :, None] + jnp.arange(rcap, dtype=jnp.int32)
        ok = (j >= starts[:, :, None]) & (j < ends[:, :, None]) & (j < n)
        flat = j.reshape(b, w * rcap)
        cand = jnp.take(store, flat, axis=0)
        dd, di = ops.candidate_topk(
            cand, ok.reshape(b, w * rcap), q, k, d_chunk=d, interpret=True
        )
        dgi = jnp.where(
            di >= 0, jnp.take_along_axis(flat, jnp.maximum(di, 0), axis=1), -1
        )
        return dd, dgi

    t_fused = timeit(lambda: fused()[0], repeats=5, warmup=1)
    t_gather = timeit(lambda: gather()[0], repeats=5, warmup=1)
    # the inter-kernel bit contract (fused == gather+dense candidate_topk,
    # global indices included), checked on the SAME closures that were just
    # timed — exact at ANY d, unlike the big-tensor jnp oracle which can sit
    # 1 ulp away at larger d (see tests/test_kernels.py)
    gd, gi = fused()
    dd, dgi = gather()
    parity = bool(np.array_equal(np.asarray(gd), np.asarray(dd))
                  and np.array_equal(np.asarray(gi), np.asarray(dgi)))
    # per-field record bytes of the pipeline-level intermediate: points(f32 d)
    # + coords(f32 2) + labels(i32) + ids(i32) + valid(bool)
    gather_bytes = b * w * rcap * (4 * d + 8 + 4 + 4 + 1)
    fused_bytes = b * k * (4 + 4)
    out = {
        "n": n, "d": d, "batch": b, "window": w, "row_cap": rcap, "k": k,
        "fused_cands_per_s": b / t_fused,
        "gather_cands_per_s": b / t_gather,
        "gather_intermediate_bytes": gather_bytes,
        "fused_intermediate_bytes": fused_bytes,
        "intermediate_bytes_reduction": gather_bytes / fused_bytes,
        "parity": parity,
    }
    csv.row("candidate_fused_csr_topk", f"N={n} B={b} w={w} cap={rcap} k={k}",
            f"{t_fused*1e6/b:.1f}", parity)
    csv.row("candidate_gather_topk", f"N={n} B={b} w={w} cap={rcap} k={k}",
            f"{t_gather*1e6/b:.1f}", parity)
    print(f"[bench_kernels] candidate-stage intermediate bytes: "
          f"{gather_bytes:,} (gather) -> {fused_bytes:,} (fused), "
          f"{out['intermediate_bytes_reduction']:.0f}x smaller", flush=True)
    return out


def bench_search_backends(rng, csv: Csv) -> list[dict]:
    """End-to-end active search: per-query vmap path vs the batched
    kernel-backed pipeline (core/batched.py).  On CPU the pallas backend runs
    interpret-mode, so its ABSOLUTE time is not hardware-meaningful — the row
    pairs exist so the same sweep on a TPU (Mosaic-compiled) reads
    out the real speedup; the end-of-row flag re-checks result parity."""
    from repro.api import ActiveSearcher, GridConfig, identity_projection

    k = 11
    rows = []
    cfg = GridConfig(grid_size=256, tile=16, n_classes=3, window=32,
                     row_cap=32, r0=10, k_slack=2.0)
    for n, b in ((20_000, 64), (100_000, 256)):
        pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)
        vmap_s = ActiveSearcher.build(
            pts, labels=labels, cfg=cfg, proj=identity_projection(pts)
        )
        pallas_s = vmap_s.with_plan(backend="pallas")
        q = jnp.asarray(rng.normal(size=(b, 2)), jnp.float32)
        t_vmap = timeit(lambda: vmap_s.search(q, k).ids, repeats=3)
        t_pal = timeit(lambda: pallas_s.search(q, k).ids, repeats=3, warmup=1)
        a = vmap_s.search(q, k)
        p = pallas_s.search(q, k)
        ok = bool(np.array_equal(np.asarray(a.ids), np.asarray(p.ids)))
        csv.row("search_vmap_jnp", f"N={n} B={b} k={k}", f"{t_vmap*1e6/b:.1f}", ok)
        csv.row("search_batched_pallas", f"N={n} B={b} k={k}", f"{t_pal*1e6/b:.1f}", ok)
        rows.append({"n": n, "batch": b, "k": k, "jnp_s": t_vmap,
                     "pallas_interpret_s": t_pal, "parity": ok})
    return rows


if __name__ == "__main__":
    main()
