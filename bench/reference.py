"""The plain reference: active search written out in numpy, and the
comparison that decides `correct`.

It imports nothing of the program and takes nothing the program made.  From
the seed it regenerates the points (`data.make_data`), projects them with the
benchmark's own exact projection, and rebuilds the grid: cells, CSR order,
offsets and the count pyramid.  For each sampled served query it then runs
the paper's Eq.-1 radius loop over the pyramid, the fixed candidate window
around the query cell, and the re-rank by l2 distance, with the arithmetic
the configuration states: float32 for every float step, exact integer
squared distances (the data are integers).

The control (`precision="bfloat16"`) is the same reference with every float
step rounded to bfloat16: the step down that a later change might be tempted
to take.  It has to come out as not correct.

Three numbers are compared, each against its limit in the configuration:

- `id_mismatch`: share of sampled queries whose served top-k differs from
  the reference at some rank, other than between distances within TIE_RTOL;
- `loop_mismatch`: share of sampled queries, among those whose loop never
  meets a rounding the float32 standard leaves open, whose served radius,
  count, iterations, convergence or truncation flag differs;
- `dist_gap`: the widest relative gap between a served distance and the
  true distance of the served id.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

TIE_RTOL = 4 * 2.0 ** -24  # two float32 distances this close rank as a tie


# ------------------------------------------------------------- the grid ----


def grid_shape(grid: dict) -> dict:
    """The derived sizes of a grid configuration (levels, padded side)."""
    t = grid["tile"]
    levels = max(1, math.ceil(math.log2(max(grid["grid_size"], t) / t)) + 1)
    padded = t * (1 << (levels - 1))
    return {"levels": levels, "padded": padded, "max_radius": padded}


def project(x, proj: dict) -> np.ndarray:
    """x @ (w / denom) (n, 2), exact: integer data, fixed-point weights."""
    w = jnp.asarray(proj["w"] / proj["denom"], jnp.float32)
    return np.asarray(jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST),
                      np.float64)


def grid_coords(g: np.ndarray, proj: dict, grid_size: int) -> np.ndarray:
    """Exact continuous grid coordinates (n, 2) of projected values g."""
    c = (g - proj["lo"][None, :]) / proj["span"][None, :] * grid_size
    c = np.clip(c, 0.0, np.float64(np.float32(grid_size - 1e-3)))
    if not np.array_equal(c.astype(np.float32).astype(np.float64), c):
        raise ValueError("grid coordinates are not exact in float32")
    return c


class Grid:
    """CSR buckets and count pyramid of the points, as the paper builds them."""

    def __init__(self, coords: np.ndarray, grid: dict):
        shape = grid_shape(grid)
        self.grid = grid
        self.levels = shape["levels"]
        self.g = shape["padded"]
        self.r_max = shape["max_radius"]
        cell = np.floor(coords).astype(np.int64)
        cid = cell[:, 0] * self.g + cell[:, 1]
        self.order = np.argsort(cid, kind="stable")
        self.n = coords.shape[0]
        self.offsets = np.searchsorted(
            cid[self.order], np.arange(self.g * self.g + 1)).astype(np.int64)
        base = np.bincount(cid, minlength=self.g * self.g)
        self.pyramid = [base.reshape(self.g, self.g).astype(np.int64)]
        for _ in range(self.levels - 1):
            s = self.pyramid[-1].shape[0] // 2
            self.pyramid.append(
                self.pyramid[-1].reshape(s, 2, s, 2).sum(axis=(1, 3)))

    def spans(self, qc: np.ndarray):
        """[start, end) CSR spans (B, w) of the window rows of each query."""
        w, g = self.grid["window"], self.g
        cx = np.floor(qc[:, 0]).astype(np.int64)
        cy = np.floor(qc[:, 1]).astype(np.int64)
        x0 = np.clip(cx - w // 2, 0, g - w)
        y0 = np.clip(cy - w // 2, 0, g - w)
        rows = x0[:, None] + np.arange(w)[None, :]
        start = self.offsets[rows * g + y0[:, None]]
        end = self.offsets[rows * g + y0[:, None] + w]
        return start, end

    def window(self, qc: np.ndarray):
        """Candidate slots in window-row-major order: CSR rows (B, w*cap)
        and their validity."""
        rcap = self.grid["row_cap"]
        start, end = self.spans(qc)
        n_pad = self.n + max(rcap - self.n, 0)
        s_cl = np.clip(start, 0, max(n_pad - rcap, 0))
        j = s_cl[:, :, None] + np.arange(rcap)[None, None, :]
        ok = (j >= start[:, :, None]) & (j < end[:, :, None]) & (j < self.n)
        b = qc.shape[0]
        return j.reshape(b, -1), ok.reshape(b, -1)


def window_bytes(grid: Grid, qc: np.ndarray, dim: int) -> np.ndarray:
    """Bytes (B,) of float32 candidate rows the window contract needs per
    query: the first row_cap points of each window row's span."""
    start, end = grid.spans(qc)
    rows = np.minimum(end - start, grid.grid["row_cap"])
    return rows.sum(axis=1).astype(np.float64) * dim * 4


# ------------------------------------------------------------ arithmetic ----


def _round_dtype(precision: str):
    if precision == "float32":
        return np.float32
    import ml_dtypes

    return ml_dtypes.bfloat16


def _spacing(x: np.ndarray) -> np.ndarray:
    return np.spacing(np.abs(x.astype(np.float32))).astype(np.float64)


def count_in_circle(grid: Grid, qc: np.ndarray, r: np.ndarray,
                    precision: str = "float32"):
    """Circle counts (B,) at each query's pyramid level, and a flag for
    lanes where float32 leaves the answer open (a cell centre on the circle
    within rounding, or a level boundary hit exactly)."""
    ft = _round_dtype(precision)
    t = grid.grid["tile"]
    b = qc.shape[0]
    # smallest level whose T-cell window holds the circle: 2**l >= 2r/(T-3)
    lv = np.zeros(b, np.int64)
    while True:
        short = (t - 3) * (2 ** lv) < 2 * r
        if not short.any():
            break
        lv = np.where(short, lv + 1, lv)
    lv = np.minimum(lv, grid.levels - 1)
    open_ = ((t - 3) * (2 ** lv) == 2 * r) & (2 * r > t - 3)
    out = np.zeros(b, np.int64)
    qx = qc[:, 0].astype(ft)
    qy = qc[:, 1].astype(ft)
    rf = r.astype(ft)
    rr = (rf * rf).astype(ft)
    ii = np.arange(t)
    for level in np.unique(lv):
        sel = np.nonzero(lv == level)[0]
        arr = grid.pyramid[level]
        s_l = arr.shape[0]
        scale = ft(2.0 ** level)
        cx = np.floor(qc[sel, 0] / 2.0 ** level).astype(np.int64)
        cy = np.floor(qc[sel, 1] / 2.0 ** level).astype(np.int64)
        ox = np.clip(cx - t // 2, 0, s_l - t)
        oy = np.clip(cy - t // 2, 0, s_l - t)
        gx = ox[:, None] + ii[None, :]                        # (b, T)
        gy = oy[:, None] + ii[None, :]
        tile = arr[gx[:, :, None], gy[:, None, :]]            # (b, T, T)
        ci = ((gx.astype(ft) + ft(0.5)) * scale).astype(ft)
        cj = ((gy.astype(ft) + ft(0.5)) * scale).astype(ft)
        dx = (ci - qx[sel, None]).astype(ft)
        dy = (cj - qy[sel, None]).astype(ft)
        d2 = ((dx * dx)[:, :, None] + (dy * dy)[:, None, :]).astype(ft)
        lim = rr[sel, None, None]
        inside = d2 <= lim
        out[sel] = np.sum(tile * inside, axis=(1, 2))
        if precision == "float32":
            # open only where float32 rounded the squared distance and the
            # exact value lies within rounding of r**2
            dx64, dy64 = dx.astype(np.float64), dy.astype(np.float64)
            exact = (dx64 * dx64)[:, :, None] + (dy64 * dy64)[:, None, :]
            near = np.abs(exact - lim.astype(np.float64)) <= 2 * _spacing(lim)
            edge = near & (exact != d2.astype(np.float64)) & (tile > 0)
            open_[sel] |= edge.any(axis=(1, 2))
    return out, open_


def radius_loop(grid: Grid, qc: np.ndarray, k: int,
                precision: str = "float32") -> dict:
    """Eq. 1, r <- round(r * sqrt(k / n)), for every query at once, with the
    iteration cap, the acceptance band and the smallest-radius fallback."""
    ft = _round_dtype(precision)
    cfg = grid.grid
    b = qc.shape[0]
    k_hi = max(k, math.ceil(k * cfg["k_slack"]))
    r_max = grid.r_max
    t = np.zeros(b, np.int64)
    r = np.full(b, cfg["r0"], np.int64)
    done = np.zeros(b, bool)
    best = np.full(b, r_max + 1, np.int64)
    n_hit = np.zeros(b, np.int64)
    open_ = np.zeros(b, bool)
    for _ in range(cfg["max_iters"]):
        act = ~done
        if not act.any():
            break
        n, amb = count_in_circle(grid, qc, r, precision)
        open_ |= amb & act
        hit = (n >= k) & (n <= k_hi)
        best_new = np.where(n >= k, np.minimum(best, r), best)
        ratio = np.sqrt((ft(k) / np.maximum(n, 1).astype(ft)).astype(ft))
        prod = (r.astype(ft) * ratio.astype(ft)).astype(ft)
        if precision == "float32":
            p64 = prod.astype(np.float64)
            half = np.abs(p64 - (np.floor(p64) + 0.5)) <= 4 * _spacing(prod)
            open_ |= half & act & ~hit & (n > 0)
        r_new = np.round(prod.astype(np.float64)).astype(np.int64)
        r_new = np.where(n == 0, r * 2, r_new)
        r_new = np.clip(r_new, 1, r_max)
        r_new = np.where((r_new == r) & ~hit, r + np.where(n < k, 1, -1), r_new)
        r_next = np.where(hit, r, np.clip(r_new, 1, r_max))
        t = np.where(act, t + 1, t)
        r = np.where(act, r_next, r)
        best = np.where(act, best_new, best)
        n_hit = np.where(act & hit, n, n_hit)
        done = np.where(act, hit, done)
    r_final = np.where(done, r, np.where(best <= r_max, best, r_max))
    n_final, amb = count_in_circle(grid, qc, r_final, precision)
    open_ |= amb & ~done
    return {"radius": r_final, "count": np.where(done, n_hit, n_final),
            "iters": t, "converged": done, "open": open_}


# ------------------------------------------------------ device arithmetic ----


def _bf16_round(x):
    """Round float32 values to the nearest bfloat16 (ties to even), written
    in integer operations so that no compiler pass can skip it."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@partial(jax.jit, static_argnames=("precision",))
def _window_dists(points, queries, rows, precision):
    """Squared l2 distances (b, C) of each query to its candidate rows,
    exact for integer data (float32), or every step rounded to bfloat16."""
    diff = points[rows] - queries[:, None, :]
    if precision == "float32":
        return jnp.sum(diff * diff, axis=-1)
    x = _bf16_round(diff * diff)
    d = x.shape[-1]
    p = 1 << max(d - 1, 0).bit_length()
    x = jnp.pad(x, ((0, 0), (0, 0), (0, p - d)))
    while p > 1:
        p //= 2
        x = _bf16_round(x[..., :p] + x[..., p:2 * p])
    return x[..., 0]


def window_sq_dists(points, queries: np.ndarray, rows: np.ndarray,
                    precision: str = "float32", block: int = 32) -> np.ndarray:
    """_window_dists over blocks of queries; rows are original point ids."""
    b = queries.shape[0]
    out = np.zeros(rows.shape, np.float64)
    for i in range(0, b, block):
        qb = queries[i:i + block]
        rb = rows[i:i + block]
        pad = block - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[-1:], pad, 0)])
            rb = np.concatenate([rb, np.repeat(rb[-1:], pad, 0)])
        s = _window_dists(points, jnp.asarray(qb, jnp.float32),
                          jnp.asarray(rb, jnp.int32), precision)
        out[i:i + block] = np.asarray(s, np.float64)[:block - pad]
    return out


def _divisor_near(n: int, want: int) -> int:
    for c in range(max(1, want), n + 1):
        if n % c == 0:
            return c
    return n


@partial(jax.jit, static_argnames=("k", "n_sub"))
def _exact_topk(points, sq_norms, queries, k, n_sub):
    qq = jnp.sum(queries * queries, axis=-1)
    dot = jnp.matmul(queries, points.T, precision=jax.lax.Precision.HIGHEST)
    s = qq[:, None] + sq_norms[None, :] - 2.0 * dot
    b, n = s.shape
    width = n // n_sub
    neg, idx = jax.lax.top_k(-s.reshape(b, n_sub, width), k)
    idx = idx + (jnp.arange(n_sub) * width)[None, :, None]
    neg2, j = jax.lax.top_k(neg.reshape(b, -1), k)
    return jnp.take_along_axis(idx.reshape(b, -1), j, axis=1)


def exact_knn(points, queries, k: int, block: int = 128) -> np.ndarray:
    """Brute-force k nearest ids (Q, k) over all points (HIGHEST precision)."""
    n = points.shape[0]
    n_sub = _divisor_near(n, int(math.sqrt(n)))
    sq = jnp.sum(points * points, axis=-1)
    q = np.asarray(queries, np.float32)
    out = []
    for i in range(0, q.shape[0], block):
        qb = q[i:i + block]
        pad = block - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[-1:], pad, 0)])
        ids = _exact_topk(points, sq, jnp.asarray(qb), k, n_sub)
        out.append(np.asarray(ids)[:block - pad])
    return np.concatenate(out)


# ----------------------------------------------------------- the search -----


def search(grid: Grid, points, queries: np.ndarray, qc: np.ndarray, k: int,
           precision: str = "float32") -> dict:
    """What the served path should return for each query: ids, distances,
    validity and the radius loop's statistics."""
    ft = _round_dtype(precision)
    loop = radius_loop(grid, qc, k, precision)
    rows, ok = grid.window(qc)
    ids_win = grid.order[np.minimum(rows, grid.n - 1)]
    sq = window_sq_dists(points, queries, ids_win, precision)
    dist = np.sqrt(sq.astype(ft)).astype(np.float64)
    dist = np.where(ok, dist, np.inf)
    rank = np.argsort(dist, axis=1, kind="stable")[:, :k]
    top_d = np.take_along_axis(dist, rank, axis=1)
    valid = np.isfinite(top_d)
    ids = np.where(valid, np.take_along_axis(ids_win, rank, axis=1), -1)
    start, end = grid.spans(qc)
    w, rcap = grid.grid["window"], grid.grid["row_cap"]
    truncated = (2 * loop["radius"] + 1 > w) | np.any(end - start > rcap, 1)
    return {"ids": ids, "dists": top_d, "valid": valid,
            "truncated": truncated, **loop}


def true_dists(points, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Exact l2 distances (float64) of each query to each listed id."""
    sq = window_sq_dists(points, queries, np.maximum(ids, 0))
    return np.sqrt(sq)


# ---------------------------------------------------------- comparison -----


LOOP_FIELDS = ("radius", "count", "iters", "converged", "truncated")


def compare(served: dict, ref: dict, served_true: np.ndarray) -> dict:
    """The three numbers compared (see the module docstring)."""
    s_ids = np.asarray(served["ids"])
    s_d = np.asarray(served["dists"], np.float64)
    s_ok = np.asarray(served["valid"], bool)
    r_d = ref["dists"]
    with np.errstate(invalid="ignore"):
        tie = np.abs(s_d - r_d) <= TIE_RTOL * np.abs(r_d)
    rank_bad = np.any((s_ids != ref["ids"]) & ~tie, axis=1)
    valid_bad = np.any(s_ok != ref["valid"], axis=1)
    srt = np.sort(np.where(s_ok, s_ids, -1 - np.arange(s_ids.shape[1])), 1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    id_bad = rank_bad | valid_bad | dup

    closed = ~ref["open"]
    loop_bad = np.zeros(s_ids.shape[0], bool)
    for f in LOOP_FIELDS:
        loop_bad |= np.asarray(served[f]).astype(np.int64) != \
            np.asarray(ref[f]).astype(np.int64)
    with np.errstate(invalid="ignore", divide="ignore"):
        gap = np.abs(s_d - served_true) / np.maximum(served_true, 1e-30)
    gap = np.where(s_ok, gap, 0.0)
    return {
        "id_mismatch": float(np.mean(id_bad)),
        "loop_mismatch": float(np.mean(loop_bad[closed])) if closed.any()
        else 1.0,
        "dist_gap": float(np.max(gap)) if gap.size else 0.0,
    }
