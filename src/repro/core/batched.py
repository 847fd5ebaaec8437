"""Batched, kernel-backed active search — the Pallas execution path.

The jnp path (`active_search.py`) runs the paper's per-query loop under
`vmap`: each query separately counts circles at its pyramid level, gathers
its CSR window row-by-row, and ranks with `lax.top_k`.  This module executes
the SAME algorithm batch-at-a-time on the purpose-built Pallas kernels so
the hot path is MXU/VPU-shaped:

  1. Eq.-1 radius adaptation for the whole batch via the LEVEL-SCHEDULED
     `kernels.ops.tile_count_multilevel` — ONE pallas_call per iteration
     that scalar-prefetches each query's (level, window) pair and DMAs its
     circle from the correct pyramid level of the flattened tile array
     (GridIndex.pyr_tiles);
  2. the candidate stage, a `CandidatePipeline` whose one entry, "fused",
     runs `kernels.ops.csr_candidate_topk`: it DMAs candidate rows straight
     from the CSR-sorted store into two VMEM row slabs, a query's whole
     window in flight while the query before it is ranked, and emits
     (dists, GLOBAL CSR indices); nothing of size (B, w*row_cap) ever
     reaches HBM, and record assembly is one (B, k) take per field.

The result is bit-for-bit identical to the jnp path (same candidate order,
same clamped spans, same first-index tie breaks; see
tests/test_batched_backend.py).  `search`/`classify` also take
`chunk_size=`: serve-scale batches stream through fixed-size kernel
invocations (one static shape, bounded VMEM) instead of materializing giant
per-batch intermediates.

This module implements the `pallas` and `pallas_q8` backends of the
`repro.api` registry, and each shard's part of the `sharded` one — hold an
`ActiveSearcher` with `ExecutionPlan(backend="pallas")` instead of calling
these entry points directly (the old `active_search.search(backend=...)`
kwarg path survives only as a deprecation shim).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import projection as proj_lib
from repro.core import pyramid as pyr
from repro.core.active_search import (
    SearchResult,
    majority_vote,
    padded_csr,
    run_chunked,
    window_cells,
    window_spans,
)
from repro.core.grid import GridConfig, GridIndex
from repro.kernels import ops


# --------------------------------------------------------------- counting ----


def batched_counts(
    index: GridIndex,
    cfg: GridConfig,
    q_grid: jax.Array,
    radii: jax.Array,
    interpret: bool | None = None,
    active: jax.Array | None = None,
) -> jax.Array:
    """Per-class circle counts (B, C) for a batch of queries/radii.

    Pyramid counter: ONE `ops.tile_count_multilevel` pallas_call — each
    query's `level_for_radius` level and window origin are scalar-prefetched,
    so every grid program DMAs its circle from the correct pyramid level of
    the flattened tile array.  No (L, B, C) stack, no L-fold overcount.

    `active` (B,) masks lanes out of the kernel: live lanes are compacted to
    a dense grid prefix and parked lanes skip their tile DMAs entirely (the
    Eq.-1 loop passes its not-yet-converged mask here).  Live rows are
    bit-identical to the unmasked call; parked rows are 0.  The sat counter
    ignores the mask — its integral-image lookup is O(1) with no DMA to
    skip.
    """
    if cfg.counter == "sat":
        from repro.core import integral as integral_lib

        return jax.vmap(lambda q, r: integral_lib.count_linf(index.sat, q, r))(
            q_grid, radii
        )

    levels = pyr.level_for_radius(radii, cfg)  # (B,) int32
    tiles = index.pyr_tiles
    if tiles is None:
        # Every index builder lays the tiles out exactly once (build_index,
        # mutable.snapshot, ActiveSearcher.from_index); re-flattening the
        # whole pyramid here would silently tax EVERY count call, so a
        # pre-layout index is an error, not a fallback.
        raise ValueError(
            "GridIndex.pyr_tiles is missing (pre-layout index): the pallas "
            "count path needs the pyramid pre-cut into T-tiles.  Wrap the "
            "index once via repro.api.ActiveSearcher.from_index(index, cfg) "
            "or set pyr_tiles=grid.flatten_pyramid_tiles(index.pyramid, "
            "cfg.tile) instead of paying a per-call re-flatten."
        )
    return ops.tile_count_multilevel(
        tiles, q_grid, radii.astype(jnp.float32), levels, cfg.tile,
        cfg.level_nblks, metric=cfg.metric, interpret=interpret,
        active=active,
    )


def radius_search_batched(
    index: GridIndex,
    cfg: GridConfig,
    q_grid: jax.Array,
    k: int,
    interpret: bool | None = None,
    adaptive_r0: bool = False,
    early_exit: bool = True,
) -> dict[str, jax.Array]:
    """Eq. 1 for a whole batch at once — all (B,) state arrays advance in one
    `while_loop` whose body is a SINGLE level-scheduled tile_count_multilevel
    call (one pallas_call per iteration, not one per pyramid level).

    Lane-for-lane identical to `vmap(pyramid.radius_search)`: finished lanes
    freeze (masked update) while the rest keep iterating.

    early_exit=True (default) passes the not-yet-converged lane mask into the
    count kernel, so converged lanes stop paying: their tile DMAs are elided
    (parked lanes alias the last live lane's resident blocks) and the post-
    loop recount only re-counts `best`-fallback lanes — the count a converged
    lane saw at its hit iteration IS the count at its final radius (the
    kernel is a deterministic integer reduction), so it is captured in the
    loop carry instead of recounted.  early_exit=False keeps the legacy
    unmasked schedule (every lane counts every iteration + one full batch
    recount); both return bit-identical results — the parity suite pins this.

    adaptive_r0=True seeds each lane's start radius from the pyramid's top
    levels (`pyramid.seed_radius`, vmapped — the same function the jnp path
    calls, so seeds match across backends by construction) instead of the
    global cfg.r0.

    Returns the Eq.-1 stats dict plus `tile_dmas_skipped`: a scalar count of
    the 2x2-cover tile DMAs the mask elided vs the always-on schedule (0 when
    early_exit=False or the counter has no tile DMAs to skip).
    """
    b = q_grid.shape[0]
    k_hi = jnp.int32(max(k, math.ceil(k * cfg.k_slack)))
    r_max = jnp.int32(cfg.max_radius)
    sentinel = r_max + 1
    # the sat counter is an O(1) integral-image lookup — no tile DMAs exist
    # to skip, so masking would only add permute traffic
    masked = early_exit and cfg.counter == "pyramid"

    def cond(state):
        t, _r, done, _best, _n_hit, _skipped = state
        return jnp.any(jnp.logical_and(t < cfg.max_iters, jnp.logical_not(done)))

    def body(state):
        t, r, done, best, n_hit, skipped = state
        active = jnp.logical_and(t < cfg.max_iters, jnp.logical_not(done))
        n = batched_counts(
            index, cfg, q_grid, r, interpret,
            active=active if masked else None,
        ).sum(axis=-1)  # (B,) — parked lanes read 0, frozen below
        hit = jnp.logical_and(n >= k, n <= k_hi)
        best_new = jnp.where(n >= k, jnp.minimum(best, r), best)
        ratio = jnp.sqrt(k / jnp.maximum(n, 1).astype(jnp.float32))
        r_new = jnp.round(r.astype(jnp.float32) * ratio).astype(jnp.int32)
        r_new = jnp.where(n == 0, r * 2, r_new)
        r_new = jnp.clip(r_new, 1, r_max)
        r_new = jnp.where(
            jnp.logical_and(r_new == r, jnp.logical_not(hit)),
            r + jnp.where(n < k, 1, -1),
            r_new,
        )
        r_next = jnp.where(hit, r, jnp.clip(r_new, 1, r_max))
        if masked:
            # 4 cover-tile DMAs per parked lane per iteration
            skipped = skipped + 4 * jnp.sum(
                jnp.logical_not(active).astype(jnp.int32)
            )
        return (
            jnp.where(active, t + 1, t),
            jnp.where(active, r_next, r),
            jnp.where(active, hit, done),
            jnp.where(active, best_new, best),
            # a lane that hits at radius r keeps r as its final radius, so
            # the in-loop count IS the final count — capture it here
            jnp.where(jnp.logical_and(active, hit), n, n_hit),
            skipped,
        )

    if adaptive_r0:
        r0 = jax.vmap(lambda g: pyr.seed_radius(index, cfg, g, k))(q_grid)
    else:
        # GridConfig rejects out-of-range r0 eagerly, so no silent clip here
        r0 = jnp.full((b,), jnp.int32(cfg.r0), jnp.int32)
    state0 = (
        jnp.zeros((b,), jnp.int32),
        r0,
        jnp.zeros((b,), bool),
        jnp.full((b,), sentinel, jnp.int32),
        jnp.zeros((b,), jnp.int32),
        jnp.int32(0),
    )
    t, r, converged, best, n_hit, skipped = jax.lax.while_loop(
        cond, body, state0
    )

    r_final = jnp.where(converged, r, jnp.where(best <= r_max, best, r_max))
    if masked:
        # converged lanes already hold their final count (n_hit); recount
        # only the best/r_max-fallback lanes whose final radius was never
        # counted as "final" in the loop
        n_re = batched_counts(
            index, cfg, q_grid, r_final, interpret,
            active=jnp.logical_not(converged),
        ).sum(axis=-1)
        n_final = jnp.where(converged, n_hit, n_re)
        skipped = skipped + 4 * jnp.sum(converged.astype(jnp.int32))
    else:
        n_final = batched_counts(
            index, cfg, q_grid, r_final, interpret
        ).sum(axis=-1)
    return {
        "radius": r_final,
        "count": n_final,
        "iters": t,
        "converged": converged,
        "tile_dmas_skipped": skipped,
    }


# -------------------------------------------------------- candidate stage ----


@dataclasses.dataclass(frozen=True)
class CandidatePipeline:
    """One pluggable candidate stage: spans in, ranked global rows out.

    select(index, cfg, q_grid, queries, spans, k, mode, radius, interpret,
           d_chunk) -> (dists (B, k) float32 with +inf pads,
                        gidx  (B, k) int32 GLOBAL CSR rows with -1 pads)

    Every pipeline must implement the SAME masking/tie-break contract as the
    per-query jnp reference (clamped span starts, row-major candidate order,
    first-index ties), so registered pipelines are interchangeable
    bit-for-bit and the facade can treat the stage as a plan detail.
    """

    name: str
    select: Callable[..., tuple[jax.Array, jax.Array]]
    description: str = ""


_CANDIDATE_PIPELINES: dict[str, CandidatePipeline] = {}


def register_candidate_pipeline(pipeline: CandidatePipeline) -> None:
    """Register (or replace) a candidate-stage pipeline under its name."""
    _CANDIDATE_PIPELINES[pipeline.name] = pipeline


def get_candidate_pipeline(name: str) -> CandidatePipeline:
    try:
        return _CANDIDATE_PIPELINES[name]
    except KeyError:
        raise ValueError(
            f"unknown candidate pipeline {name!r}; registered: "
            f"{sorted(_CANDIDATE_PIPELINES)}"
        ) from None


def _fused_select(index, cfg, q_grid, queries, spans, k, mode, radius,
                  interpret, d_chunk):
    """csr_candidate_topk: DMA candidate rows straight from the CSR store —
    the only HBM traffic the stage produces is the (B, k) result pair."""
    pts, crd, _lab, _ids, n, _n_pad = padded_csr(index, cfg.row_cap)
    start, end = spans
    if mode == "paper":
        return ops.csr_candidate_topk(
            crd, start, end, q_grid, k, n, cfg.row_cap, metric=cfg.metric,
            radii=radius.astype(jnp.float32), center_cells=True,
            d_chunk=d_chunk, interpret=interpret,
        )
    return ops.csr_candidate_topk(
        pts, start, end, queries.astype(jnp.float32), k, n, cfg.row_cap,
        metric=cfg.metric, d_chunk=d_chunk, interpret=interpret,
    )


register_candidate_pipeline(CandidatePipeline(
    name="fused",
    select=_fused_select,
    description="csr_candidate_topk: whole-window row DMAs from the CSR "
                "store, the next query's in flight, no (B, w*row_cap, d) "
                "HBM intermediate",
))


# ---------------------------------------------------- quantized (q8) stage ---


def q8_shortlist(
    index: GridIndex,
    store,  # QuantizedStore
    cfg: GridConfig,
    queries: jax.Array,
    rerank_k: int,
    spans: tuple[jax.Array, jax.Array] | None = None,
    interpret: bool | None = None,
    d_chunk: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The coarse int8 stage alone: approx scores + global CSR shortlist.

    Exposed for tests and the accuracy bench (shortlist-hit-fraction
    instrumentation); `search_q8` is the full coarse->re-rank path.
    """
    q_grid = proj_lib.to_grid_coords(index.proj, queries, cfg.grid_size)
    start, end = spans if spans is not None else window_spans(index, cfg, q_grid)
    n = index.points_sorted.shape[0]
    return ops.csr_shortlist_q8(
        store.q_points, store.row_scales, start, end,
        queries.astype(jnp.float32), rerank_k, n, cfg.row_cap,
        metric=cfg.metric, d_chunk=d_chunk, interpret=interpret,
    )


def _q8_select(index, store, cfg, q_grid, queries, spans, k, rerank_k, mode,
               radius, interpret, d_chunk):
    """int8 coarse shortlist -> exact fp32 re-rank of `rerank_k` rows.

    NOT a CandidatePipeline: the pipeline registry promises bit-parity
    interchange, and the q8 stage promises recall instead (ISSUE: recall@k
    contract + conditional bit-parity).  Paper mode delegates to the exact
    fused stage — it ranks 2-d cell CENTERS, which are integer-plus-half by
    construction, so there is no bandwidth to win by quantizing them.

    Re-rank invariance: the shortlist is sorted ascending by global CSR row
    before the exact re-rank, so `candidate_topk`'s first-index tie-break
    means lowest-global-row — exactly the fused kernel's tie-break (its
    window enumerates valid rows in ascending CSR order).  With the same
    d_chunk decomposition both paths compute the identical
    `sqrt(max(sum, 0))`, so whenever the shortlist contains the exact
    top-k, the re-ranked (dists, gidx) are bit-identical to `pallas`
    (tests/test_quantized.py pins this).
    """
    if mode == "paper":
        return _fused_select(index, cfg, q_grid, queries, spans, k, mode,
                             radius, interpret, d_chunk)
    pts, _crd, _lab, _ids, _n, n_pad = padded_csr(index, cfg.row_cap)
    sld, sli = q8_shortlist(
        index, store, cfg, queries, rerank_k, spans=spans,
        interpret=interpret, d_chunk=d_chunk,
    )
    del sld  # approx scores only ordered the shortlist; re-rank is exact
    # stable ascending sort by global row, -1 pads parked last (n_pad is
    # strictly greater than any live row index)
    order = jnp.argsort(jnp.where(sli >= 0, sli, n_pad), axis=1)
    sl = jnp.take_along_axis(sli, order, axis=1)          # (B, rerank_k)
    valid = sl >= 0
    cand = jnp.take(pts, jnp.maximum(sl, 0), axis=0)      # (B, rerank_k, d)
    rd = pts.shape[-1]
    # mirror the fused kernel's decomposition (d_chunk=None -> one sum) so
    # float accumulation order matches bit-for-bit
    dc = rd if d_chunk is None else max(1, min(d_chunk, rd))
    outd, outi = ops.candidate_topk(
        cand, valid, queries.astype(jnp.float32), k,
        metric=cfg.metric, d_chunk=dc, interpret=interpret,
    )
    gidx = jnp.take_along_axis(sl, jnp.maximum(outi, 0), axis=1)
    return outd, jnp.where(outi >= 0, gidx, -1)


def resolve_rerank_k(cfg: GridConfig, k: int, rerank_k: int | None) -> int:
    """The shortlist length the q8 path actually runs with.

    None -> min(max(4k, 32), window*row_cap): deep enough that the exact
    top-k survives approximate ordering at CI configs, capped at the window
    (a shortlist cannot out-run its candidate pool).  Explicit values are
    validated eagerly: rerank_k < k can never return k exact rows.
    """
    cap = cfg.window * cfg.row_cap
    if rerank_k is None:
        return min(max(4 * k, 32), cap)
    if rerank_k < k:
        raise ValueError(
            f"rerank_k={rerank_k} < k={k}: the exact re-rank can only "
            f"return rows the shortlist contains"
        )
    return min(rerank_k, cap)


# ------------------------------------------------- stages of one search call -
#
# Each stage of the jitted search programs runs under a `jax.named_scope`
# (`search.project`, `search.radius_loop`, `search.window`,
# `search.candidates`, `search.records`).  A scope only names the ops in
# their HLO metadata (op_name "jit(_search_impl)/search.radius_loop/..."),
# so a profiler trace can give each device op its stage; the compiled
# program is otherwise the same.


def _project(index: GridIndex, cfg: GridConfig, queries: jax.Array):
    with jax.named_scope("search.project"):
        return proj_lib.to_grid_coords(index.proj, queries, cfg.grid_size)


def _radius_loop(index, cfg, q_grid, k, interpret, adaptive_r0):
    with jax.named_scope("search.radius_loop"):
        return radius_search_batched(
            index, cfg, q_grid, k, interpret, adaptive_r0=adaptive_r0
        )


def shard_window_spans(index, cfg, q_grid, shard, n_shards):
    """One shard's part of a window that one index over ALL shards' points
    would read: (this shard's CSR spans, the whole index's spans), each
    (start, end) of shape (B, w).

    One index keeps the first `row_cap` records of each window row's
    span, in global CSR order (cell-major, arrival order inside a cell).
    A cell lives on exactly one shard (`cell % n_shards`), so on each shard
    the kept records are a prefix of its own span of the row: all of its
    records in the cells before the cut cell (the cell of the record at
    global position start + row_cap), and the cut cell's first
    `start + row_cap - global_offsets[cut]` records if the shard owns it.
    `index.offsets`, `.global_offsets` and `.global_cells` are the shard's
    (1, M) blocks of the stacked arrays (core/distributed.py); `shard` may
    be traced (`lax.axis_index`)."""
    loff, goff = index.offsets, index.global_offsets
    first, end = window_cells(cfg, q_grid)                 # (B, w)
    g_start, g_end = goff[0, first], goff[0, end]
    cap = g_start + jnp.int32(cfg.row_cap)
    # past the last record global_cells reads padded_size**2 >= end: the
    # row holds at most row_cap records and the cut is `end` itself
    last = index.global_cells.shape[1] - 1
    cut = jnp.minimum(index.global_cells[0, jnp.minimum(cap, last)], end)
    owned = (cut < end) & (cut % n_shards == shard)
    local_end = loff[0, cut] + jnp.where(owned, cap - goff[0, cut], 0)
    return (loff[0, first], local_end), (g_start, g_end)


def _locate(index, cfg, queries, k, interpret, adaptive_r0, shard=None):
    """Projection, the Eq.-1 loop and the CSR window: (q_grid (B, 2), the
    loop's stats, (start, end) spans (B, w), truncated (B,)).  `shard`
    (index, count) takes a shard's part of one index's window
    (`shard_window_spans`)."""
    q_grid = _project(index, cfg, queries)
    stats = _radius_loop(index, cfg, q_grid, k, interpret, adaptive_r0)
    with jax.named_scope("search.window"):
        r = stats["radius"]
        if shard is None:
            spans = whole = window_spans(index, cfg, q_grid)
        else:
            spans, whole = shard_window_spans(index, cfg, q_grid, *shard)
        truncated = ((2 * r + 1) > jnp.int32(cfg.window)) | jnp.any(
            whole[1] - whole[0] > jnp.int32(cfg.row_cap), axis=-1
        )
    return q_grid, stats, spans, truncated


def _assemble(index, cfg, outd, outi, stats, truncated) -> SearchResult:
    """Record assembly: one (B, k) take per field from the padded CSR
    arrays."""
    with jax.named_scope("search.records"):
        _pts, _crd, lab, ids, _n, _n_pad = padded_csr(index, cfg.row_cap)
        sel_valid = jnp.isfinite(outd)
        idx = jnp.maximum(outi, 0)
        return SearchResult(
            ids=jnp.where(sel_valid, jnp.take(ids, idx), -1),
            dists=outd.astype(jnp.float32),
            labels=jnp.where(sel_valid, jnp.take(lab, idx), -1),
            valid=sel_valid,
            radius=stats["radius"],
            count=stats["count"],
            iters=stats["iters"],
            converged=stats["converged"],
            truncated=truncated,
        )


def _vote(index, cfg, q_grid, res, k, interpret):
    """classify's answer from a search's records: the majority label, or
    the class counts of the final circle where the window came back short
    or truncated (the jnp path's graceful degradation, counted by the
    kernel)."""
    with jax.named_scope("search.records"):
        refined = majority_vote(res.labels, res.valid, cfg.n_classes)
        fallback = jnp.argmax(
            batched_counts(index, cfg, q_grid, res.radius, interpret), axis=-1
        ).astype(jnp.int32)
        short = jnp.sum(res.valid.astype(jnp.int32), axis=1) < k
        return jnp.where(short | res.truncated, fallback, refined)


def _paper_vote(index, cfg, queries, k, interpret, adaptive_r0):
    """classify in paper mode: the majority class of the final circle's
    counts."""
    q_grid = _project(index, cfg, queries)
    stats = _radius_loop(index, cfg, q_grid, k, interpret, adaptive_r0)
    with jax.named_scope("search.records"):
        counts = batched_counts(index, cfg, q_grid, stats["radius"], interpret)
        return jnp.argmax(counts, axis=-1).astype(jnp.int32)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "rerank_k", "mode", "interpret", "d_chunk", "adaptive_r0",
    ),
)
def _search_q8_impl(
    index: GridIndex,
    store,  # QuantizedStore
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    rerank_k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> SearchResult:
    q_grid, stats, spans, truncated = _locate(
        index, cfg, queries, k, interpret, adaptive_r0
    )
    with jax.named_scope("search.candidates"):
        outd, outi = _q8_select(
            index, store, cfg, q_grid, queries, spans, k, rerank_k, mode,
            stats["radius"], interpret, d_chunk,
        )
    return _assemble(index, cfg, outd, outi, stats, truncated)


def search_q8(
    index: GridIndex,
    store,  # QuantizedStore (core.quantized.quantize_index(index, cfg))
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    rerank_k: int | None = None,
    interpret: bool | None = None,
    chunk_size: int | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> SearchResult:
    """Quantized-candidate active search (the `pallas_q8` backend).

    Identical counting/span stages to `search`; the candidate stage DMAs
    the int8 store, shortlists top-`rerank_k` by approximate int32 scores,
    then exact-re-ranks the shortlist against fp32 rows.  Final (dists,
    ids) are full fp32 — approximate only in WHICH rows made the shortlist
    (recall contract; see docs/API.md).  Paper mode is exact (cell centers
    gain nothing from quantization)."""
    rk = resolve_rerank_k(cfg, k, rerank_k)
    return run_chunked(
        lambda q: _search_q8_impl(index, store, cfg, q, k, rk, mode,
                                  interpret, d_chunk, adaptive_r0),
        queries,
        chunk_size,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "rerank_k", "mode", "interpret", "d_chunk", "adaptive_r0",
    ),
)
def _classify_q8_impl(
    index: GridIndex,
    store,  # QuantizedStore
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    rerank_k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> jax.Array:
    if cfg.n_classes <= 0:
        raise ValueError("classify() needs an index built with n_classes > 0")

    if mode == "paper":
        return _paper_vote(index, cfg, queries, k, interpret, adaptive_r0)

    q_grid = _project(index, cfg, queries)
    res = _search_q8_impl(index, store, cfg, queries, k, rerank_k,
                          mode="refined", interpret=interpret, d_chunk=d_chunk,
                          adaptive_r0=adaptive_r0)
    return _vote(index, cfg, q_grid, res, k, interpret)


def classify_q8(
    index: GridIndex,
    store,  # QuantizedStore
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    rerank_k: int | None = None,
    interpret: bool | None = None,
    chunk_size: int | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> jax.Array:
    """Quantized-candidate kNN classification (the `pallas_q8` backend) —
    `classify`'s contract with `search_q8` as the refined-vote stage."""
    rk = resolve_rerank_k(cfg, k, rerank_k)
    return run_chunked(
        lambda q: _classify_q8_impl(index, store, cfg, q, k, rk, mode,
                                    interpret, d_chunk, adaptive_r0),
        queries,
        chunk_size,
    )


# -------------------------------------------------------------- entry points -


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "mode", "interpret", "pipeline", "d_chunk", "adaptive_r0",
    ),
)
def _search_impl(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    pipeline: CandidatePipeline | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> SearchResult:
    # `pipeline` is the RESOLVED CandidatePipeline (frozen, hashed by its
    # fields, so re-registering a name retraces instead of silently serving
    # the stale jit cache); the public wrappers resolve names eagerly.
    if pipeline is None:
        pipeline = get_candidate_pipeline("fused")
    q_grid, stats, spans, truncated = _locate(
        index, cfg, queries, k, interpret, adaptive_r0
    )
    with jax.named_scope("search.candidates"):
        outd, outi = pipeline.select(
            index, cfg, q_grid, queries, spans, k, mode, stats["radius"],
            interpret, d_chunk,
        )
    return _assemble(index, cfg, outd, outi, stats, truncated)


def shard_search(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str,
    interpret: bool | None,
    d_chunk: int | None,
    adaptive_r0: bool,
    shard: jax.Array,
    n_shards: int,
) -> tuple[jax.Array, SearchResult]:
    """One shard's part of a sharded search, traced inside its shard_map
    (core/distributed.py): the `pallas` stages and kernels over this
    shard's records, with the radius loop on the global pyramid and the
    window cut to this shard's part of one index's window.  Returns
    (q_grid, the shard's top-k with the loop's stats and the whole index's
    `truncated` flag)."""
    q_grid, stats, spans, truncated = _locate(
        index, cfg, queries, k, interpret, adaptive_r0,
        shard=(shard, n_shards),
    )
    with jax.named_scope("search.candidates"):
        outd, outi = _fused_select(
            index, cfg, q_grid, queries, spans, k, mode, stats["radius"],
            interpret, d_chunk,
        )
    return q_grid, _assemble(index, cfg, outd, outi, stats, truncated)


def search(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    chunk_size: int | None = None,
    pipeline: str = "fused",
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> SearchResult:
    """Batched kernel-backed active search: queries (B, d) -> SearchResult
    with leading B.  Same result contract as the facade's
    `ActiveSearcher.search` (repro.api), which is how callers should reach
    this path (`ExecutionPlan(backend="pallas")`).

    chunk_size streams the batch through fixed-size kernel invocations (one
    static shape, bounded VMEM) — results are bit-identical for any value.
    adaptive_r0 seeds each query's Eq.-1 start radius from the pyramid
    (`ExecutionPlan(adaptive_r0=True)` is the facade spelling).
    """
    pipe = get_candidate_pipeline(pipeline)  # eager: bad names raise here
    return run_chunked(
        lambda q: _search_impl(index, cfg, q, k, mode, interpret, pipe,
                               d_chunk, adaptive_r0),
        queries,
        chunk_size,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "mode", "interpret", "pipeline", "d_chunk", "adaptive_r0",
    ),
)
def _classify_impl(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    pipeline: CandidatePipeline | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> jax.Array:
    if cfg.n_classes <= 0:
        raise ValueError("classify() needs an index built with n_classes > 0")

    if mode == "paper":
        return _paper_vote(index, cfg, queries, k, interpret, adaptive_r0)

    q_grid = _project(index, cfg, queries)
    res = _search_impl(index, cfg, queries, k, mode="refined",
                       interpret=interpret, pipeline=pipeline, d_chunk=d_chunk,
                       adaptive_r0=adaptive_r0)
    return _vote(index, cfg, q_grid, res, k, interpret)


def classify(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    interpret: bool | None = None,
    chunk_size: int | None = None,
    pipeline: str = "fused",
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> jax.Array:
    """Batched kNN classification — same result contract as the facade's
    `ActiveSearcher.classify` (repro.api), with every count pass going
    through the level-scheduled tile_count_multilevel kernel."""
    pipe = get_candidate_pipeline(pipeline)  # eager: bad names raise here
    return run_chunked(
        lambda q: _classify_impl(index, cfg, q, k, mode, interpret, pipe,
                                 d_chunk, adaptive_r0),
        queries,
        chunk_size,
    )
