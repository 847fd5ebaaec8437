"""Exact brute-force kNN — the paper's comparator ("original kNN").

Blocked over the datastore so memory stays bounded at any N: a lax.scan over
N-chunks keeps a running top-k per query (the same streaming-top-k pattern the
kernels/brute_knn Pallas kernel uses on TPU).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class ExactResult(NamedTuple):
    ids: jax.Array    # (B, k) int32
    dists: jax.Array  # (B, k) float32


def _pairwise(q: jax.Array, x: jax.Array, metric: str) -> jax.Array:
    """(B, d) x (N, d) -> (B, N) distances."""
    if metric == "l1":
        return jnp.sum(jnp.abs(q[:, None, :] - x[None, :, :]), axis=-1)
    # ||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2  (MXU-friendly form).  HIGHEST
    # keeps the matmul in full float32 on a TPU, whose default precision
    # rounds the operands to bfloat16; the CPU computes float32 either way.
    qq = jnp.sum(q * q, axis=-1, keepdims=True)
    xx = jnp.sum(x * x, axis=-1)
    qx = jnp.matmul(q, x.T, precision=lax.Precision.HIGHEST)
    d2 = qq - 2.0 * qx + xx[None, :]
    return jnp.sqrt(jnp.maximum(d2, 0.0))


@partial(jax.jit, static_argnames=("k", "metric", "block"))
def knn(
    queries: jax.Array,
    points: jax.Array,
    k: int,
    metric: str = "l2",
    block: int = 4096,
) -> ExactResult:
    """Exact kNN of `queries` (B, d) against `points` (N, d)."""
    q = queries.astype(jnp.float32)
    x = points.astype(jnp.float32)
    b, _ = q.shape
    n = x.shape[0]

    if n <= block:
        d = _pairwise(q, x, metric)
        neg, idx = lax.top_k(-d, min(k, n))
        if k > n:  # pad to k
            padd = jnp.full((b, k - n), jnp.inf, jnp.float32)
            padi = jnp.full((b, k - n), -1, jnp.int32)
            return ExactResult(
                jnp.concatenate([idx.astype(jnp.int32), padi], axis=1),
                jnp.concatenate([-neg, padd], axis=1),
            )
        return ExactResult(idx.astype(jnp.int32), -neg)

    # streaming top-k over blocks
    nb = -(-n // block)
    n_pad = nb * block
    xp = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    xb = xp.reshape(nb, block, -1)

    def step(carry, inp):
        best_d, best_i = carry
        blk, off = inp
        d = _pairwise(q, blk, metric)                       # (B, block)
        ids = off + jnp.arange(block, dtype=jnp.int32)
        d = jnp.where(ids[None, :] < n, d, jnp.inf)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, (b, block))], axis=1)
        neg, sel = lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, sel, axis=1)), None

    init = (jnp.full((b, k), jnp.inf, jnp.float32), jnp.full((b, k), -1, jnp.int32))
    offs = (jnp.arange(nb, dtype=jnp.int32) * block)
    (best_d, best_i), _ = lax.scan(step, init, (xb, offs))
    return ExactResult(best_i, best_d)


@partial(jax.jit, static_argnames=("k", "n_classes", "metric", "block"))
def classify(
    queries: jax.Array,
    points: jax.Array,
    labels: jax.Array,
    k: int,
    n_classes: int,
    metric: str = "l2",
    block: int = 4096,
) -> jax.Array:
    """Exact kNN majority-vote classification — the paper's ground truth."""
    res = knn(queries, points, k, metric=metric, block=block)
    neigh = labels[jnp.clip(res.ids, 0, labels.shape[0] - 1)]
    onehot = jax.nn.one_hot(neigh, n_classes, dtype=jnp.float32)
    votes = jnp.sum(onehot * jnp.isfinite(res.dists)[..., None], axis=1)
    return jnp.argmax(votes, axis=-1).astype(jnp.int32)
