"""Active search for nearest neighbors — the paper's algorithm, end to end.

Pipeline per query (DESIGN.md §2):
  1. project the query into grid space (projection.py)
  2. adapt the radius with Eq. 1 over the count pyramid (pyramid.py)
  3. gather candidates from the CSR buckets inside a fixed window around the
     query cell (per-row contiguous slices — row-major cell ids make each
     window row ONE contiguous span of `points_sorted`)
  4. either return circle members (paper-faithful) or re-rank candidates by
     the true metric in the original space (refined mode)

All functions are jit/vmap friendly; fixed shapes throughout.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import projection as proj_lib
from repro.core import pyramid as pyr
from repro.core.grid import GridConfig, GridIndex
from repro.kernels.rank import metric_distance


class SearchResult(NamedTuple):
    ids: jax.Array        # (k,) int32 — global point ids (-1 where invalid)
    dists: jax.Array      # (k,) float32 — distance in the ORIGINAL space (inf where invalid)
    labels: jax.Array     # (k,) int32
    valid: jax.Array      # (k,) bool
    radius: jax.Array     # () int32 — final Eq.-1 radius (pixels)
    count: jax.Array      # () int32 — points inside the final circle
    iters: jax.Array      # () int32
    converged: jax.Array  # () bool — Eq. 1 hit the acceptance band
    truncated: jax.Array  # () bool — candidates were dropped: the circle
    # exceeded the candidate window, OR a window row held more than row_cap
    # points (the gather keeps only the first row_cap of each row's span)


class Candidates(NamedTuple):
    points: jax.Array   # (C, d) float32
    coords: jax.Array   # (C, 2) float32 grid coords
    labels: jax.Array   # (C,) int32
    ids: jax.Array      # (C,) int32
    valid: jax.Array    # (C,) bool


def _metric_dist(a: jax.Array, b: jax.Array, metric: str) -> jax.Array:
    # the kernels' fixed summation order, so every backend ranks with the
    # same float32 distances (kernels/rank.py)
    return metric_distance(a - b, metric)[..., 0]


def majority_vote(labels: jax.Array, valid: jax.Array, n_classes: int) -> jax.Array:
    """(B, k) neighbor labels + validity -> (B,) argmax class votes.

    The one vote used by every classify path (jnp, pallas, sharded)."""

    def one(lab, ok):
        onehot = jax.nn.one_hot(lab, n_classes, dtype=jnp.float32)
        return jnp.argmax(jnp.sum(onehot * ok[:, None], axis=0)).astype(jnp.int32)

    return jax.vmap(one)(labels, valid)


def run_chunked(fn, queries, chunk_size: int | None):
    """Stream a batched query pipeline through fixed-size chunks.

    `queries` is an array — or any pytree of arrays sharing a leading batch
    axis (e.g. (q_grid, radii) pairs).  Calls `fn` on chunk_size-row slices
    (the last chunk is padded to full size by repeating its final row, so
    every kernel invocation keeps ONE static shape / VMEM footprint) and
    concatenates the per-chunk output pytrees.  Every query is computed
    exactly as in the unchunked call — all per-lane state in the pipeline is
    independent across the batch — so results are bit-identical for any
    chunk_size.
    """
    if chunk_size is not None and chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    b = jax.tree.leaves(queries)[0].shape[0]
    if b == 0:
        # An empty batch would otherwise reach the pipeline (or the
        # pad-by-last-row broadcast) with a zero-size leading axis; derive
        # the output pytree abstractly from a 1-row probe and return empty,
        # correctly-shaped leaves instead of invoking any kernel.
        probe = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((1,) + a.shape[1:], a.dtype), queries
        )
        out = jax.eval_shape(fn, probe)
        return jax.tree.map(
            lambda s: jnp.zeros((0,) + s.shape[1:], s.dtype), out
        )
    if not chunk_size or b <= chunk_size:
        return fn(queries)
    outs = []
    for i in range(0, b, chunk_size):
        chunk = jax.tree.map(lambda a: a[i : i + chunk_size], queries)
        pad = chunk_size - jax.tree.leaves(chunk)[0].shape[0]
        if pad:
            chunk = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])]
                ),
                chunk,
            )
        outs.append(fn(chunk))
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0)[:b], *outs)


def padded_csr(index: GridIndex, rcap: int):
    """CSR record arrays padded so a row_cap slice is always in bounds.

    Returns (points, coords, labels, ids, n, n_pad); pad ids are -1.
    """
    n = index.points_sorted.shape[0]
    pad = max(rcap - n, 0)
    if pad:
        pts = jnp.pad(index.points_sorted, ((0, pad), (0, 0)))
        crd = jnp.pad(index.coords_sorted, ((0, pad), (0, 0)))
        lab = jnp.pad(index.labels_sorted, (0, pad))
        ids = jnp.pad(index.ids_sorted, (0, pad), constant_values=-1)
    else:
        pts, crd, lab, ids = (
            index.points_sorted,
            index.coords_sorted,
            index.labels_sorted,
            index.ids_sorted,
        )
    return pts, crd, lab, ids, n, n + pad


def window_cells(cfg: GridConfig, q_grid: jax.Array):
    """Flat cell ids [first, end) of the w window rows around each query
    cell: q_grid (..., 2) -> first, end (..., w), end = first + w."""
    g = cfg.padded_size
    w = cfg.window
    cx = jnp.floor(q_grid[..., 0]).astype(jnp.int32)
    cy = jnp.floor(q_grid[..., 1]).astype(jnp.int32)
    x0 = jnp.clip(cx - w // 2, 0, g - w)
    y0 = jnp.clip(cy - w // 2, 0, g - w)
    rows = x0[..., None] + jnp.arange(w, dtype=jnp.int32)   # (..., w)
    return rows * g + y0[..., None], rows * g + (y0[..., None] + w)


def window_spans(index: GridIndex, cfg: GridConfig, q_grid: jax.Array):
    """CSR [start, end) spans of the w window rows around each query cell.

    q_grid (..., 2) -> start, end (..., w) — shape-polymorphic, so the same
    math serves the per-query path (q_grid (2,)) and the batched path
    (q_grid (B, 2), core/batched.py).
    """
    first, end = window_cells(cfg, q_grid)
    return index.offsets[first], index.offsets[end]


def gather_candidates(index: GridIndex, cfg: GridConfig, q_grid: jax.Array) -> Candidates:
    """Fixed-shape CSR gather of the window around the query cell.

    Window rows are contiguous spans of the CSR arrays (row-major cell ids),
    so each row costs one dynamic_slice of `row_cap` records.
    """
    w, rcap = cfg.window, cfg.row_cap
    d = index.points_sorted.shape[1]
    pts, crd, lab, ids, n, n_pad = padded_csr(index, rcap)
    start, end = window_spans(index, cfg, q_grid)            # (w,), (w,)

    def per_row(s, e):
        s_cl = jnp.clip(s, 0, max(n_pad - rcap, 0))
        j = s_cl + jnp.arange(rcap, dtype=jnp.int32)
        p = lax.dynamic_slice(pts, (s_cl, 0), (rcap, d))
        c = lax.dynamic_slice(crd, (s_cl, 0), (rcap, 2))
        lb = lax.dynamic_slice(lab, (s_cl,), (rcap,))
        gid = lax.dynamic_slice(ids, (s_cl,), (rcap,))
        ok = (j >= s) & (j < e) & (j < n)
        return p, c, lb, gid, ok

    p, c, lb, gid, ok = jax.vmap(per_row)(start, end)
    flat = lambda a: a.reshape((w * rcap,) + a.shape[2:])
    return Candidates(flat(p), flat(c), flat(lb), flat(gid), flat(ok))


def _topk_result(
    cand: Candidates,
    dists: jax.Array,
    k: int,
    stats: dict[str, jax.Array],
    truncated: jax.Array,
) -> SearchResult:
    masked = jnp.where(cand.valid, dists, jnp.inf)
    k_eff = min(k, masked.shape[0])
    neg_top, idx = lax.top_k(-masked, k_eff)
    if k_eff < k:  # k exceeds the candidate window: pad with invalid slots
        pad = k - k_eff
        neg_top = jnp.concatenate([neg_top, jnp.full((pad,), -jnp.inf)], axis=0)
        idx = jnp.concatenate([idx, jnp.zeros((pad,), idx.dtype)], axis=0)
    top_d = -neg_top
    sel_valid = jnp.isfinite(top_d)
    return SearchResult(
        ids=jnp.where(sel_valid, cand.ids[idx], -1),
        dists=top_d.astype(jnp.float32),
        labels=jnp.where(sel_valid, cand.labels[idx], -1),
        valid=sel_valid,
        radius=stats["radius"],
        count=stats["count"],
        iters=stats["iters"],
        converged=stats["converged"],
        truncated=truncated,
    )


@partial(jax.jit, static_argnames=("cfg", "k", "mode", "adaptive_r0"))
def search_one(
    index: GridIndex, cfg: GridConfig, query: jax.Array, k: int,
    mode: str = "refined", adaptive_r0: bool = False,
) -> SearchResult:
    """Active search for ONE query point (original space, shape (d,)).

    mode="paper":   members of the final circle, ranked by grid-pixel distance
                    (the paper returns the circle contents when n == k).
    mode="refined": candidates re-ranked by the true metric in the original
                    space (exact kNN restricted to the window; recommended).
    adaptive_r0:    seed Eq. 1 from the pyramid's local-density sketch
                    (pyramid.seed_radius) instead of the global cfg.r0.
    """
    q_grid = proj_lib.to_grid_coords(index.proj, query, cfg.grid_size)
    stats = pyr.radius_search(index, cfg, q_grid, k, adaptive_r0=adaptive_r0)
    r = stats["radius"]
    # the flag must fire whenever candidates were DROPPED: circle wider than
    # the window, or a window row overflowing its row_cap slice (same rule,
    # same span math, as the batched backends)
    start, end = window_spans(index, cfg, q_grid)
    truncated = ((2 * r + 1) > jnp.int32(cfg.window)) | jnp.any(
        end - start > jnp.int32(cfg.row_cap)
    )

    cand = gather_candidates(index, cfg, q_grid)
    if mode == "paper":
        centers = jnp.floor(cand.coords) + 0.5
        gd = _metric_dist(centers, q_grid[None, :], cfg.metric)
        in_circle = gd <= r.astype(jnp.float32)
        cand = cand._replace(valid=cand.valid & in_circle)
        return _topk_result(cand, gd, k, stats, truncated)

    dists = _metric_dist(cand.points, query[None, :].astype(jnp.float32), cfg.metric)
    return _topk_result(cand, dists, k, stats, truncated)


@partial(jax.jit, static_argnames=("cfg", "k", "mode", "adaptive_r0"))
def _search_jnp(
    index: GridIndex, cfg: GridConfig, queries: jax.Array, k: int,
    mode: str = "refined", adaptive_r0: bool = False,
) -> SearchResult:
    return jax.vmap(
        lambda q: search_one(index, cfg, q, k, mode, adaptive_r0)
    )(queries)


def _deprecated_searcher(index, cfg, backend, interpret, chunk_size, what):
    """Shared shim plumbing: warn once per call site, build the facade."""
    from repro.core import engine

    warnings.warn(
        f"active_search.{what}(backend=/interpret=/chunk_size=) is "
        f"deprecated; build a repro.api.ActiveSearcher with an "
        f"ExecutionPlan instead (results are bit-identical)",
        DeprecationWarning,
        stacklevel=3,
    )
    plan = engine.ExecutionPlan(
        backend=backend, interpret=interpret, chunk_size=chunk_size
    )
    return engine.ActiveSearcher.from_index(index, cfg, plan=plan)


def search(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    backend: str = "jnp",
    interpret: bool | None = None,
    chunk_size: int | None = None,
) -> SearchResult:
    """DEPRECATED shim — use `repro.api.ActiveSearcher.search`.

    Delegates to the facade (`core/engine.py`), which resolves `backend`
    from the registry and carries interpret/chunk_size in an ExecutionPlan;
    results are bit-identical to the pre-facade path.  Kept so existing
    call sites and tests keep passing.
    """
    return _deprecated_searcher(
        index, cfg, backend, interpret, chunk_size, "search"
    ).search(queries, k, mode=mode)


@partial(jax.jit, static_argnames=("cfg", "k", "mode", "adaptive_r0"))
def _classify_jnp(
    index: GridIndex, cfg: GridConfig, queries: jax.Array, k: int,
    mode: str = "refined", adaptive_r0: bool = False,
) -> jax.Array:
    if cfg.n_classes <= 0:
        raise ValueError("classify() needs an index built with n_classes > 0")

    if mode == "paper":

        def one(q):
            q_grid = proj_lib.to_grid_coords(index.proj, q, cfg.grid_size)
            stats = pyr.radius_search(
                index, cfg, q_grid, k, adaptive_r0=adaptive_r0
            )
            counts = pyr.count_in_circle(index, cfg, q_grid, stats["radius"])
            return jnp.argmax(counts).astype(jnp.int32)

        return jax.vmap(one)(queries)

    res = _search_jnp(index, cfg, queries, k, mode="refined",
                      adaptive_r0=adaptive_r0)
    refined = majority_vote(res.labels, res.valid, cfg.n_classes)

    # graceful degradation: when the data is so sparse that the Eq.-1 circle
    # outruns the candidate window (res.truncated / <k valid candidates), the
    # window vote is under-sampled — fall back to the paper's count-based
    # argmax at the final radius for THOSE queries only.
    def count_pred(q, r):
        q_grid = proj_lib.to_grid_coords(index.proj, q, cfg.grid_size)
        return jnp.argmax(pyr.count_in_circle(index, cfg, q_grid, r)).astype(jnp.int32)

    fallback = jax.vmap(count_pred)(queries, res.radius)
    short = jnp.sum(res.valid.astype(jnp.int32), axis=1) < k
    return jnp.where(short | res.truncated, fallback, refined)


def classify(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mode: str = "refined",
    backend: str = "jnp",
    interpret: bool | None = None,
    chunk_size: int | None = None,
) -> jax.Array:
    """DEPRECATED shim — use `repro.api.ActiveSearcher.classify`.

    mode="paper":   argmax of per-class counts inside the final circle — pure
                    count comparison on the class channels, exactly Fig. 2.
    mode="refined": majority vote over the refined top-k labels.
    Delegates to the facade (`core/engine.py`); bit-identical results.
    """
    return _deprecated_searcher(
        index, cfg, backend, interpret, chunk_size, "classify"
    ).classify(queries, k, mode=mode)
