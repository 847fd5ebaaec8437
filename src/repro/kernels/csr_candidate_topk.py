"""Pallas TPU kernel: fused CSR-gather -> distance -> streaming top-k.

`candidate_topk` ranks candidates that a separate gather stage already
materialized as a dense (B, w*row_cap, d) tensor in HBM — four full-field
`jnp.take`s (points/coords/labels/ids) whose rows are mostly padding
(`valid` masks the slack).  This kernel retires that intermediate: each
query-program reads its window spans from scalar-prefetched SMEM and DMAs
candidate rows DIRECTLY from the CSR-sorted store (which never leaves HBM)
into a double-buffered VMEM scratch, so the only thing the candidate stage
ever writes back is the (B, k) result.

Per grid program (one query):

  1. warm-up DMA of window row 0 (`row_cap` store rows starting at the
     clamped span start) into buffer slot 0;
  2. for each of the `w` window rows: kick off the NEXT row's DMA into the
     other slot, wait on the current slot, compute the metric distance of
     its `row_cap` rows against the query on the VPU (`rank.metric_distance`,
     the fixed-order sum every ranking path shares), and write (masked
     distance, global CSR row index) into column `row` of a (row_cap, w)
     accumulator pair carried in registers — invalid slots (outside
     [start, end), past the live CSR length, or outside the paper-mode
     circle) get +inf;
  3. run the streaming (min, argmin, mask) top-k over the accumulator
     (`rank.streaming_topk`; k is small, so k vector passes beat a sort),
     emitting distances and GLOBAL CSR indices, so record assembly
     downstream is one (B, k) take per field instead of four (B, w*row_cap)
     gathers.

Masking/tie-break contract is IDENTICAL to gather_candidates_batched +
candidate_topk lane for lane (same candidate order, same clamped span
starts, first-index argmin ties), so the fused path is bit-for-bit with the
gather path and with the per-query jnp reference on the CPU.
`center_cells=True` + `radii` reproduce mode="paper" (rank floor(coords)+0.5
cell centers, mask to the final Eq.-1 circle).  Tested in interpret mode against
ref.csr_candidate_topk, and compiled for v5e by tests/test_tpu_compile.py.

Per program: the (1, d) query and (1, k) outputs are blocks of (B, 1, d) and
(B, 1, k) arrays (the (8, 128) block rule holds for the two minor dims),
and VMEM holds 2 * row_cap * d floats of row buffer — independent of B and
of N, which is what lets serve-scale batches stream through fixed-size
invocations while the store scales to millions of points.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rank import metric_distance, streaming_topk


def _kernel(
    span_ref,   # scalar prefetch: (B, 2w) int32 — [starts | ends] CSR spans
    rad_ref,    # scalar prefetch: (B,) float32 — Eq.-1 radii (paper mode)
    q_ref,      # (1, d) float32 — this query's ranking vector
    store_ref,  # (n_pad, d) float32 — CSR-sorted store, stays in HBM/ANY
    outd_ref,   # (1, k) float32
    outi_ref,   # (1, k) int32 — global CSR row indices (-1 where invalid)
    buf_ref,    # scratch (2, row_cap, d) float32 — double-buffered rows
    sem,        # DMA semaphores (2,)
    *,
    w: int,
    row_cap: int,
    k: int,
    n: int,
    n_pad: int,
    d_chunk: int | None,
    metric: str,
    center_cells: bool,
    use_radius: bool,
):
    i = pl.program_id(0)
    q = q_ref[...]                            # (1, d)
    r = rad_ref[i]
    s_max = max(n_pad - row_cap, 0)

    def s_cl(row):
        # same clamp as the gather path: a span start near the end of the
        # store still yields an in-bounds row_cap slice
        return jnp.clip(span_ref[i, row], 0, s_max)

    def row_dma(slot, row):
        return pltpu.make_async_copy(
            store_ref.at[pl.ds(s_cl(row), row_cap)],
            buf_ref.at[slot],
            sem.at[slot],
        )

    row_dma(0, 0).start()
    # the (row_cap, w) accumulator holds window row `row` in column `row`
    col = jax.lax.broadcasted_iota(jnp.int32, (row_cap, w), 1)
    off = jax.lax.broadcasted_iota(jnp.int32, (row_cap, 1), 0)

    def body(row, acc):
        dacc, gacc = acc
        slot = jax.lax.rem(row, 2)

        @pl.when(row + 1 < w)
        def _prefetch_next():
            row_dma(jax.lax.rem(row + 1, 2), row + 1).start()

        row_dma(slot, row).wait()
        rows = buf_ref[slot]                  # (row_cap, d)
        if center_cells:                      # paper mode ranks cell centers
            rows = jnp.floor(rows) + 0.5
        dist = metric_distance(rows - q, metric, d_chunk)   # (row_cap, 1)
        j = s_cl(row) + off
        ok = (j >= span_ref[i, row]) & (j < span_ref[i, w + row]) & (j < n)
        if use_radius:
            ok = ok & (dist <= r)
        here = col == row
        return (jnp.where(here, jnp.where(ok, dist, jnp.inf), dacc),
                jnp.where(here, j, gacc))

    dacc, gacc = jax.lax.fori_loop(
        0, w, body,
        (jnp.full((row_cap, w), jnp.inf, jnp.float32),
         jnp.zeros((row_cap, w), jnp.int32)),
    )
    # candidates rank in window-row-major order, as in the gather path
    outd_ref[...], outi_ref[...] = streaming_topk(
        dacc, col * row_cap + off, gacc, k
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n", "row_cap", "metric", "center_cells", "d_chunk", "interpret"
    ),
)
def csr_candidate_topk(
    store: jax.Array,    # (n_pad, d) float32 — CSR-sorted ranking vectors
    starts: jax.Array,   # (B, w) int32 — window-row span starts
    ends: jax.Array,     # (B, w) int32 — window-row span ends
    queries: jax.Array,  # (B, d) float32 — per-query ranking vectors
    k: int,
    n: int,              # live CSR rows (store rows >= n are padding)
    row_cap: int,
    metric: str = "l2",
    radii: jax.Array | None = None,  # (B,) float32 — paper-mode circle mask
    center_cells: bool = False,      # rank floor(store)+0.5 cell centers
    d_chunk: int | None = None,      # split the d-accumulation (None = one sum)
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Contract identical to ref.csr_candidate_topk.

    Returns (dists (B, k) float32 with +inf pads, idx (B, k) int32 GLOBAL
    CSR row indices with -1 pads).  `n_pad = store.shape[0]` must be
    >= row_cap (pad the store first — see active_search.padded_csr).
    """
    n_pad, d = store.shape
    b, w = starts.shape
    if n_pad < row_cap:
        raise ValueError(
            f"store has {n_pad} rows but row_cap={row_cap}; pad the store "
            f"(active_search.padded_csr) so every span slice is in bounds"
        )
    if ends.shape != (b, w):
        raise ValueError(f"ends shape {ends.shape} != starts {starts.shape}")
    if queries.shape != (b, d):
        # the grid is sized from the spans; a short queries array would have
        # its block index clamped and silently rank trailing spans against a
        # repeated query instead of failing
        raise ValueError(
            f"queries shape {queries.shape} does not match spans batch "
            f"{b} x store dim {d}"
        )
    if radii is not None and radii.shape != (b,):
        raise ValueError(
            f"radii shape {radii.shape} does not match spans batch ({b},)"
        )
    spans = jnp.concatenate([starts, ends], axis=1).astype(jnp.int32)
    rad = (
        jnp.zeros((b,), jnp.float32) if radii is None
        else radii.astype(jnp.float32)
    )
    kernel = functools.partial(
        _kernel,
        w=w, row_cap=row_cap, k=k, n=n, n_pad=n_pad, d_chunk=d_chunk,
        metric=metric, center_cells=center_cells,
        use_radius=radii is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            # (B, 1, d) with the batch dim squeezed: each program sees its
            # query as a (1, d) block, which the (8, 128) rule accepts
            pl.BlockSpec((None, 1, d), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # store: manual DMA only
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda i, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, row_cap, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, k), jnp.int32),
        ],
        interpret=interpret,
        name="csr_candidate_topk",
    )(spans, rad, queries.astype(jnp.float32)[:, None, :],
      store.astype(jnp.float32))
    return outd[:, 0], outi[:, 0]
