"""Candidate ranking shared by the kernels, their oracles and the jnp path.

Float addition is not associative, so `jnp.sum` leaves the summation order
to whoever lowers it: XLA's CPU and TPU backends, Mosaic, and the Pallas
interpreter each pick their own, and the same distances can then differ by
an ulp between the jnp pipeline, a kernel, and its oracle.  Every
candidate-ranking path (the fused and dense kernels, `ref.py`, and the jnp
pipeline in `core/active_search.py`) reduces the feature axis through
`tree_sum` instead: a pairwise halving tree of elementwise adds over static
slices.  Elementwise adds are never reassociated, so all of them produce the
same bits on the CPU, and on Mosaic the tree lowers to lane shifts.

`streaming_topk` is the in-kernel selection: k rounds of (min, first-index
argmin, mask) over a 2-D candidate block, written with whole-block vector
reductions only (no dynamic scalar reads, no 1-D arrays), which is what
Mosaic accepts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def tree_sum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Sum over `axis` (kept, size 1) by pairwise halving.

    A width that is not a power of two is zero-padded to the next one; adding
    a zero is exact, so any zero tail gives the same bits as no tail.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    p = 1 << max(n - 1, 0).bit_length()
    if p != n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, p - n)
        x = jnp.pad(x, pad)
    while p > 1:
        p //= 2
        x = (lax.slice_in_dim(x, 0, p, axis=axis)
             + lax.slice_in_dim(x, p, 2 * p, axis=axis))
    return x


def metric_accumulate(
    diff: jax.Array, metric: str, d_chunk: int | None = None, axis: int = -1
) -> jax.Array:
    """Coordinate differences -> sums of |diff| (l1) or diff**2 (l2) over
    the feature `axis` (kept, size 1), before the l2 square root.

    `d_chunk` splits the feature axis into chunks that are tree-summed
    separately and then added in order; None sums the whole axis as one
    tree.  The sums depend only on which features are added, not on where
    the axis lies, so a kernel may lay candidates along lanes.
    """
    # XLA's CPU backend contracts a product that feeds an add into one FMA
    # inside loop bodies (interpreted kernels, lax.map chunks) but not in
    # straight-line code, which changes the last bit.  The max is the
    # identity on squares and keeps each square rounded before the tree.
    x = jnp.abs(diff) if metric == "l1" else jnp.maximum(diff * diff, 0.0)
    axis = axis % x.ndim
    d = x.shape[axis]
    dc = d if d_chunk is None else max(1, min(d_chunk, d))
    acc = tree_sum(lax.slice_in_dim(x, 0, dc, axis=axis), axis)
    for c0 in range(dc, d, dc):
        acc = acc + tree_sum(
            lax.slice_in_dim(x, c0, min(c0 + dc, d), axis=axis), axis
        )
    return acc


def finish_distance(acc: jax.Array, metric: str) -> jax.Array:
    """The metric's distance from its accumulated sum."""
    return acc if metric == "l1" else jnp.sqrt(jnp.maximum(acc, 0.0))


def metric_distance(
    diff: jax.Array, metric: str, d_chunk: int | None = None, axis: int = -1
) -> jax.Array:
    """Coordinate differences -> l1 or l2 distances over the feature `axis`
    (kept, size 1)."""
    return finish_distance(
        metric_accumulate(diff, metric, d_chunk, axis), metric
    )


def streaming_topk(
    dist: jax.Array,   # (R, W) float32 — +inf marks an invalid candidate
    order: jax.Array,  # (R, W) int32 — distinct candidate positions
    gidx: jax.Array,   # (R, W) int32 — what to report for each candidate
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """The k smallest distances, ties to the lowest `order`.

    Returns ((1, k) float32 distances with +inf pads, (1, k) int32 `gidx`
    values with -1 pads): the contract of `lax.top_k(-dist)` over the
    candidates laid out flat in `order`.
    """
    kk = lax.broadcasted_iota(jnp.int32, (1, k), 1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def pick(t, carry):
        dcur, outd, outi = carry
        m = jnp.min(dcur)
        first = jnp.min(jnp.where(dcur == m, order, big))
        hit = order == first
        g = jnp.min(jnp.where(hit, gidx, big))
        outd = jnp.where(kk == t, m, outd)
        outi = jnp.where(kk == t, jnp.where(m < jnp.inf, g, -1), outi)
        return jnp.where(hit, jnp.inf, dcur), outd, outi

    _, outd, outi = lax.fori_loop(
        0, k, pick,
        (dist, jnp.full((1, k), jnp.inf, jnp.float32),
         jnp.full((1, k), -1, jnp.int32)),
    )
    return outd, outi
