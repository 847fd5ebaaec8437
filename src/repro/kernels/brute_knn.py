"""Pallas TPU kernel: blocked exact kNN (the paper's baseline, done right).

Streaming formulation so the (B, N) distance matrix never exists in HBM:
grid = (B-blocks, N-blocks); each step computes one (bq, bn) distance block
on the MXU (||q||^2 - 2 q.x + ||x||^2) and folds it into a running top-k that
lives in VMEM scratch across the sequential N-block axis — the same pattern
flash-attention uses for its running softmax.

MXU alignment: bq and bn default to 128/512; d is the contraction dim.
Validated with interpret=True against ref.brute_knn.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    q_ref,    # (bq, d) float32
    x_ref,    # (bn, d) float32
    outd_ref,  # (bq, k) float32
    outi_ref,  # (bq, k) int32
    bestd_ref,  # scratch (bq, k) float32
    besti_ref,  # scratch (bq, k) int32
    *,
    k: int,
    bn: int,
    nn: int,
    n: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        bestd_ref[...] = jnp.full_like(bestd_ref, jnp.inf)
        besti_ref[...] = jnp.full_like(besti_ref, -1)

    q = q_ref[...]
    x = x_ref[...]
    qq = jnp.sum(q * q, axis=1, keepdims=True)            # (bq, 1)
    xx = jnp.sum(x * x, axis=1)[None, :]                  # (1, bn)
    cross = jnp.dot(q, x.T, preferred_element_type=jnp.float32)
    d = jnp.sqrt(jnp.maximum(qq - 2.0 * cross + xx, 0.0))  # (bq, bn)

    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    d = jnp.where(ids < n, d, jnp.inf)

    cat_d = jnp.concatenate([bestd_ref[...], d], axis=1)   # (bq, k + bn)
    cat_i = jnp.concatenate([besti_ref[...], ids], axis=1)
    col = jax.lax.broadcasted_iota(jnp.int32, cat_d.shape, 1)
    new_d, new_i = [], []
    for _ in range(k):
        m = jnp.min(cat_d, axis=1)                         # (bq,)
        am = jnp.argmin(cat_d, axis=1)                     # (bq,)
        new_d.append(m)
        new_i.append(jnp.take_along_axis(cat_i, am[:, None], axis=1)[:, 0])
        cat_d = jnp.where(col == am[:, None], jnp.inf, cat_d)
    bestd_ref[...] = jnp.stack(new_d, axis=1)
    besti_ref[...] = jnp.stack(new_i, axis=1)

    @pl.when(j == nn - 1)
    def _emit():
        outd_ref[...] = bestd_ref[...]
        outi_ref[...] = jnp.where(
            jnp.isfinite(bestd_ref[...]), besti_ref[...], -1
        )


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_n", "interpret")
)
def brute_knn(
    queries: jax.Array,  # (B, d)
    points: jax.Array,   # (N, d)
    k: int,
    block_q: int = 128,
    block_n: int = 512,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Contract identical to ref.brute_knn (ids of padded rows are -1/inf)."""
    q = queries.astype(jnp.float32)
    x = points.astype(jnp.float32)
    b, d = q.shape
    n = x.shape[0]
    bq = min(block_q, b)
    bn = min(block_n, n)
    nb = -(-b // bq)
    nn = -(-n // bn)
    q = jnp.pad(q, ((0, nb * bq - b), (0, 0)))
    x = jnp.pad(x, ((0, nn * bn - n), (0, 0)))

    kernel = functools.partial(_kernel, k=k, bn=bn, nn=nn, n=n)
    outd, outi = pl.pallas_call(
        kernel,
        grid=(nb, nn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * bq, k), jnp.float32),
            jax.ShapeDtypeStruct((nb * bq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, x)
    return outd[:b], outi[:b]
