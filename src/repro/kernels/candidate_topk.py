"""Pallas TPU kernel: fused candidate distances + streaming top-k.

After the radius loop, active search has <=C candidate points per query
(gathered from the CSR buckets).  This kernel fuses the distance computation
with k-selection so candidate distances never round-trip to HBM: distances
accumulate over d-chunks in a (C, 1) VMEM scratch (each chunk summed by the
shared fixed-order `rank.metric_accumulate`), and the final chunk runs k
iterations of (min, argmin, mask) — k is small, so k vector passes beat a
full sort by a wide margin.

Grid = (B, d_chunks); the d-chunk axis is the minormost (sequential on TPU),
so the scratch accumulator legally persists across chunk steps.
Tested in interpret mode against ref.candidate_topk, and compiled for v5e by
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rank import finish_distance, metric_accumulate, streaming_topk


def _kernel(
    cand_ref,   # (C, dc) float32
    q_ref,      # (1, dc) float32
    valid_ref,  # (C, 1) int32
    outd_ref,   # (1, k) float32
    outi_ref,   # (1, k) int32
    acc_ref,    # scratch (C, 1) float32
    *,
    k: int,
    nd: int,
    metric: str,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += metric_accumulate(cand_ref[...] - q_ref[...], metric)

    @pl.when(j == nd - 1)
    def _select():
        d = finish_distance(acc_ref[...], metric)         # (C, 1)
        d = jnp.where(valid_ref[...] > 0, d, jnp.inf)
        slot = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        outd_ref[...], outi_ref[...] = streaming_topk(d, slot, slot, k)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "d_chunk", "interpret")
)
def candidate_topk(
    candidates: jax.Array,  # (B, C, d) float32
    valid: jax.Array,       # (B, C) bool
    queries: jax.Array,     # (B, d) float32
    k: int,
    metric: str = "l2",
    d_chunk: int = 512,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Contract identical to ref.candidate_topk."""
    b, c, d = candidates.shape
    dc = min(d_chunk, d)
    nd = -(-d // dc)
    d_pad = nd * dc
    if d_pad != d:
        candidates = jnp.pad(candidates, ((0, 0), (0, 0), (0, d_pad - d)))
        queries = jnp.pad(queries, ((0, 0), (0, d_pad - d)))

    kernel = functools.partial(_kernel, k=k, nd=nd, metric=metric)
    # per-query blocks squeeze the batch dim of (B, ·, ·) arrays, so the two
    # minor dims of every block satisfy the (8, 128) rule
    outd, outi = pl.pallas_call(
        kernel,
        grid=(b, nd),
        in_specs=[
            pl.BlockSpec((None, c, dc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, dc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, c, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((c, 1), jnp.float32)],
        interpret=interpret,
        name="candidate_topk",
    )(
        candidates.astype(jnp.float32),
        queries.astype(jnp.float32)[:, None, :],
        valid.astype(jnp.int32)[:, :, None],
    )
    return outd[:, 0], outi[:, 0]
