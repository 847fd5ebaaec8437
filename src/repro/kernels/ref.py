"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the exact contract of its kernel in ops.py; kernel tests
sweep shapes/dtypes and compare against these.  The candidate-ranking oracles
sum distances with `rank.metric_distance`, the fixed accumulation order the
kernels use, so on the CPU they match the kernels bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.rank import metric_distance


def tile_count(
    level_arr: jax.Array,   # (S, S, C) int32 — one pyramid level
    queries: jax.Array,     # (B, 2) float32 — positions in BASE-pixel units
    radii: jax.Array,       # (B,) float32 — radii in base-pixel units
    scale: int,             # 2**level
    tile: int,              # T — window side in level cells
    metric: str = "l2",
) -> jax.Array:
    """Circle-masked counts (B, C): count of points whose level-cell center
    lies within radius of the query.  Matches pyramid._count_at_level."""
    s = level_arr.shape[0]

    def one(q, r):
        cx = jnp.floor(q[0] / scale).astype(jnp.int32)
        cy = jnp.floor(q[1] / scale).astype(jnp.int32)
        ox = jnp.clip(cx - tile // 2, 0, s - tile)
        oy = jnp.clip(cy - tile // 2, 0, s - tile)
        window = lax.dynamic_slice(level_arr, (ox, oy, 0), (tile, tile, level_arr.shape[-1]))
        ci = (ox + jnp.arange(tile, dtype=jnp.float32) + 0.5) * scale
        cj = (oy + jnp.arange(tile, dtype=jnp.float32) + 0.5) * scale
        if metric == "l1":
            mask = (jnp.abs(ci - q[0])[:, None] + jnp.abs(cj - q[1])[None, :]) <= r
        else:
            d2 = (ci - q[0])[:, None] ** 2 + (cj - q[1])[None, :] ** 2
            mask = d2 <= r * r
        return jnp.sum(window * mask[:, :, None].astype(jnp.int32), axis=(0, 1))

    return jax.vmap(one)(queries.astype(jnp.float32), radii.astype(jnp.float32))


def tile_count_multilevel(
    pyramid: tuple[jax.Array, ...],  # level l: (S_l, S_l, C) int32
    queries: jax.Array,              # (B, 2) float32, base-pixel units
    radii: jax.Array,                # (B,) float32, base-pixel units
    levels: jax.Array,               # (B,) int32 pyramid level per query
    tile: int,
    metric: str = "l2",
) -> jax.Array:
    """Level-scheduled counts (B, C): each query counted at its OWN pyramid
    level — the stacked-select oracle for kernels.tile_count_multilevel."""
    per_level = jnp.stack(
        [
            tile_count(arr, queries, radii, 1 << lv, tile, metric=metric)
            for lv, arr in enumerate(pyramid)
        ],
        axis=0,
    )  # (L, B, C)
    lv = jnp.clip(levels.astype(jnp.int32), 0, len(pyramid) - 1)
    return jnp.take_along_axis(per_level, lv[None, :, None], axis=0)[0]


def candidate_topk(
    candidates: jax.Array,  # (B, C, d) float32
    valid: jax.Array,       # (B, C) bool
    queries: jax.Array,     # (B, d) float32
    k: int,
    metric: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Top-k smallest distances among valid candidates.
    Returns dists (B, k) float32 (inf when <k valid) and idx (B, k) int32
    (candidate row index, -1 when invalid)."""
    d = metric_distance(candidates - queries[:, None, :], metric)[..., 0]
    d = jnp.where(valid, d, jnp.inf)
    neg, idx = lax.top_k(-d, k)
    dists = -neg
    return dists, jnp.where(jnp.isfinite(dists), idx.astype(jnp.int32), -1)


def csr_candidate_topk(
    store: jax.Array,    # (n_pad, d) float32 — CSR-sorted ranking vectors
    starts: jax.Array,   # (B, w) int32 window-row span starts
    ends: jax.Array,     # (B, w) int32 window-row span ends
    queries: jax.Array,  # (B, d) float32
    k: int,
    n: int,              # live CSR rows
    row_cap: int,
    metric: str = "l2",
    radii: jax.Array | None = None,  # (B,) float32 paper-mode circle mask
    center_cells: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused-gather oracle: materialize the (B, w*row_cap) window the way
    gather_candidates_batched does, rank with candidate_topk's contract, and
    map the selected slots back to GLOBAL CSR row indices.
    Returns dists (B, k) float32 (inf pads) and idx (B, k) int32 (-1 pads)."""
    n_pad = store.shape[0]
    b, w = starts.shape
    s_cl = jnp.clip(starts, 0, max(n_pad - row_cap, 0))          # (B, w)
    j = s_cl[:, :, None] + jnp.arange(row_cap, dtype=jnp.int32)  # (B, w, cap)
    ok = (j >= starts[:, :, None]) & (j < ends[:, :, None]) & (j < n)
    flat = j.reshape(b, w * row_cap)
    cand = jnp.take(store, flat, axis=0)                 # (B, w*cap, d)
    if center_cells:
        cand = jnp.floor(cand) + 0.5
    d = metric_distance(
        cand - queries[:, None, :].astype(jnp.float32), metric
    )[..., 0]
    valid = ok.reshape(b, w * row_cap)
    if radii is not None:
        valid = valid & (d <= radii[:, None].astype(jnp.float32))
    d = jnp.where(valid, d, jnp.inf)
    k_eff = min(k, d.shape[1])
    neg, idx = lax.top_k(-d, k_eff)
    if k_eff < k:  # k exceeds the window: pad like the kernel does
        pad = k - k_eff
        neg = jnp.concatenate([neg, jnp.full((b, pad), -jnp.inf)], axis=1)
        idx = jnp.concatenate([idx, jnp.zeros((b, pad), idx.dtype)], axis=1)
    dists = -neg
    gidx = jnp.take_along_axis(flat, idx, axis=1)
    return dists, jnp.where(jnp.isfinite(dists), gidx, -1)


def csr_shortlist_q8(
    q_store: jax.Array,     # (n_pad, d) int8 — quantized CSR store
    row_scales: jax.Array,  # (n_pad,) float32 — per-row cell scales
    starts: jax.Array,      # (B, w) int32 window-row span starts
    ends: jax.Array,        # (B, w) int32 window-row span ends
    queries: jax.Array,     # (B, d) float32
    rerank_k: int,
    n: int,                 # live CSR rows
    row_cap: int,
    metric: str = "l2",
    d_chunk: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Oracle for the int8 shortlist kernel (csr_candidate_topk_q8).

    The scoring is integer-deterministic, so this is an EXACT-match oracle
    (same clip/round/chunked accumulation as the kernel), not an allclose
    one.  Returns approx scores (B, rerank_k) float32 with +inf pads and
    GLOBAL CSR row indices (B, rerank_k) int32 with -1 pads, best-first.
    """
    from repro.kernels.csr_candidate_topk_q8 import QCLIP, q8_d_chunks

    n_pad, dim = q_store.shape
    b, w = starts.shape
    s_cl = jnp.clip(starts, 0, max(n_pad - row_cap, 0))          # (B, w)
    j = s_cl[:, :, None] + jnp.arange(row_cap, dtype=jnp.int32)  # (B, w, cap)
    ok = (j >= starts[:, :, None]) & (j < ends[:, :, None]) & (j < n)
    flat = j.reshape(b, w * row_cap)
    cand = jnp.take(q_store, flat, axis=0).astype(jnp.int32)  # (B, C, d)
    s = jnp.take(row_scales, flat, axis=0)[:, :, None]        # (B, C, 1)
    qs = jnp.clip(
        jnp.round(queries.astype(jnp.float32)[:, None, :] / s), -QCLIP, QCLIP
    ).astype(jnp.int32)
    diff = cand - qs
    chunks = q8_d_chunks(dim, d_chunk)
    if metric == "l1":
        acc = sum(
            jnp.sum(jnp.abs(diff[:, :, c0:c0 + dc]), axis=-1)
            for c0, dc in chunks
        )
        d = s[:, :, 0] * acc.astype(jnp.float32)
    else:
        acc = sum(
            jnp.sum(
                diff[:, :, c0:c0 + dc] * diff[:, :, c0:c0 + dc], axis=-1
            ).astype(jnp.float32)
            for c0, dc in chunks
        )
        d = s[:, :, 0] * jnp.sqrt(acc)
    d = jnp.where(ok.reshape(b, w * row_cap), d, jnp.inf)
    neg, idx = lax.top_k(-d, rerank_k)
    dists = -neg
    gidx = jnp.take_along_axis(flat, idx, axis=1)
    return dists, jnp.where(jnp.isfinite(dists), gidx, -1)


def brute_knn(
    queries: jax.Array,  # (B, d) float32
    points: jax.Array,   # (N, d) float32
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Exact L2 kNN.  Returns dists (B, k) ascending and ids (B, k) int32."""
    q = queries.astype(jnp.float32)
    x = points.astype(jnp.float32)
    d2 = (
        jnp.sum(q * q, axis=-1, keepdims=True)
        - 2.0 * (q @ x.T)
        + jnp.sum(x * x, axis=-1)[None, :]
    )
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    neg, idx = lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def flash_attention(
    q: jax.Array,   # (B, S, H, hd)
    k: jax.Array,   # (B, T, H, hd)
    v: jax.Array,
    causal: bool = True,
) -> jax.Array:
    """Plain softmax attention — the flash_attention oracle."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s_ = jnp.einsum("bshk,bthk->bhst", qf, kf) / jnp.sqrt(q.shape[-1])
    if causal:
        sq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(tk)[None, :]
        s_ = jnp.where(mask[None, None], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhst,bthk->bshk", p, vf).astype(q.dtype)
