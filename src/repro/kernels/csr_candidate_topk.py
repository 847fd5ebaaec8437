"""Pallas TPU kernel: fused CSR-gather -> distance -> streaming top-k.

`candidate_topk` ranks candidates that a separate gather stage already
materialized as a dense (B, w*row_cap, d) tensor in HBM — four full-field
`jnp.take`s (points/coords/labels/ids) whose rows are mostly padding
(`valid` masks the slack).  This kernel retires that intermediate: each
query-program reads its window spans from scalar-prefetched SMEM and DMAs
candidate rows DIRECTLY from the CSR-sorted store (which never leaves HBM)
into two VMEM row slabs, so the only thing the candidate stage ever writes
back is the (B, k) result.

DMA schedule.  A window row is a small copy (row_cap x d floats, 16 KB at
sift shapes) whose round trip costs far more than its bytes, so the kernel
keeps many of them in flight.  The rows of every query, in query order,
are cut into groups of `depth = rows_in_flight(w, row_cap, d)` window rows
(a query's whole window whenever two such slabs fit `SLAB_BUDGET_BYTES`),
and the groups alternate between the two slabs.  One DMA semaphore per
slab counts the group's bytes.  The grid runs its programs in order, so a
group's rows are started while the group before it is ranked; the first
program also starts its own first group, and the last starts nothing past
its window.  A wait on a slab waits for its whole group, since a shared
semaphore counts bytes, not rows.

Per grid program (one query), for each of its groups:

  1. start the next group's row DMAs (the next program's first group after
     the last one) into the other slab;
  2. wait on this group's slab, then rank the whole resident group at once:
     its (rows, d) block minus the query is transposed to (d, rows), so
     candidates lie along lanes and `rank.metric_distance` (the fixed-order
     sum every ranking path shares) reduces the feature axis along
     sublanes — whole-vreg adds in place of one dependent chain of lane
     shifts per window row, with the same pairs added in the same order;
  3. spread each window row's span over its slots, 128 candidates at a
     time, and mask (distance, global CSR row index) — invalid slots
     (outside [start, end), past the live CSR length, or outside the
     paper-mode circle) get +inf;

then run the streaming (min, argmin, mask) top-k over the (1, w*row_cap)
window (`rank.streaming_topk`; k is small, so k vector passes beat a
sort), emitting distances and GLOBAL CSR indices, so record assembly
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
and VMEM holds the two slabs, 2 * depth * row_cap * d floats — independent
of B and of N, which is what lets serve-scale batches stream through
fixed-size invocations while the store scales to millions of points.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rank import metric_distance, streaming_topk

# VMEM the two row slabs may take; a whole sift window (w = row_cap = 32,
# d = 128) takes 1 MiB for both
SLAB_BUDGET_BYTES = 4 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def rows_in_flight(
    w: int, row_cap: int, d: int, budget: int = SLAB_BUDGET_BYTES
) -> int:
    """Window rows per slab: w when two slabs of a whole window fit
    `budget` bytes of VMEM (rows padded to the (8, 128) float32 tiling),
    else the fewest groups that fit, as evenly filled as possible (at
    least one row each)."""
    row_bytes = _round_up(row_cap, 8) * _round_up(d, 128) * 4
    fit = max(1, min(w, budget // (2 * row_bytes)))
    return -(-w // -(-w // fit))


def _kernel(
    span_ref,   # scalar prefetch: (B, 2w) int32 — [starts | ends] CSR spans
    rad_ref,    # scalar prefetch: (B,) float32 — Eq.-1 radii (paper mode)
    q_ref,      # (1, d) float32 — this query's ranking vector
    store_ref,  # (n_pad, d) float32 — CSR-sorted store, stays in HBM/ANY
    outd_ref,   # (1, k) float32
    outi_ref,   # (1, k) int32 — global CSR row indices (-1 where invalid)
    buf_ref,    # scratch (2, depth * row_cap, d) float32 — the two slabs
    sem,        # DMA semaphores (2,), one per slab
    *,
    w: int,
    depth: int,
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
    groups = -(-w // depth)
    q = q_ref[...]                            # (1, d)
    r = rad_ref[i]
    s_max = max(n_pad - row_cap, 0)

    def s_cl(qi, row):
        # same clamp as the gather path: a span start near the end of the
        # store still yields an in-bounds row_cap slice
        return jnp.clip(span_ref[qi, row], 0, s_max)

    def group_dmas(qi, g, slab):
        lo = g * depth
        return [
            pltpu.make_async_copy(
                store_ref.at[pl.ds(s_cl(qi, row), row_cap)],
                buf_ref.at[slab, pl.ds((row - lo) * row_cap, row_cap)],
                sem.at[slab],
            )
            for row in range(lo, min(lo + depth, w))
        ]

    def start(dmas):
        for dma in dmas:
            dma.start()

    @pl.when(i == 0)
    def _first_group():
        start(group_dmas(0, 0, 0))

    dists, gidx = [], []
    for g in range(groups):
        # groups alternate slabs across the whole batch, in query order
        slab = jax.lax.rem(i * groups + g, 2)
        if g + 1 < groups:
            start(group_dmas(i, g + 1, 1 - slab))
        else:
            @pl.when(i + 1 < pl.num_programs(0))
            def _next_query():
                start(group_dmas(i + 1, 0, 1 - slab))

        for dma in group_dmas(i, g, slab):
            dma.wait()
        lo, hi = g * depth, min(g * depth + depth, w)
        m = (hi - lo) * row_cap
        rows = buf_ref[slab, pl.ds(0, m), :]   # (m, d): the group's rows
        if center_cells:                       # paper mode ranks cell centers
            rows = jnp.floor(rows) + 0.5
        # candidates along lanes: the feature tree adds whole vregs
        dist = metric_distance((rows - q).T, metric, d_chunk, axis=0)  # (1, m)
        for t0 in range(0, m, 128):
            c = lo * row_cap + t0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, min(128, m - t0)), 1)   # window-flat slots
            # each window row's span, spread over the row's slots
            first = (lo * row_cap + t0) // row_cap
            last = (lo * row_cap + t0 + c.shape[1] - 1) // row_cap
            base = s_cl(i, first) - first * row_cap
            st, en = span_ref[i, first], span_ref[i, w + first]
            for row in range(first + 1, last + 1):
                here = c >= row * row_cap
                base = jnp.where(here, s_cl(i, row) - row * row_cap, base)
                st = jnp.where(here, span_ref[i, row], st)
                en = jnp.where(here, span_ref[i, w + row], en)
            j = base + c                           # global CSR rows
            ok = (j >= st) & (j < en) & (j < n)
            dt = dist[:, t0:t0 + c.shape[1]]
            if use_radius:
                ok = ok & (dt <= r)
            dists.append(jnp.where(ok, dt, jnp.inf))
            gidx.append(j)
    dacc = jnp.concatenate(dists, axis=1)          # (1, w * row_cap)
    gacc = jnp.concatenate(gidx, axis=1)
    # candidates rank in window-row-major order, as in the gather path
    outd_ref[...], outi_ref[...] = streaming_topk(
        dacc, jax.lax.broadcasted_iota(jnp.int32, dacc.shape, 1), gacc, k
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
    depth = rows_in_flight(w, row_cap, d)
    kernel = functools.partial(
        _kernel,
        w=w, depth=depth, row_cap=row_cap, k=k, n=n, n_pad=n_pad,
        d_chunk=d_chunk, metric=metric, center_cells=center_cells,
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
            pltpu.VMEM((2, depth * row_cap, d), jnp.float32),
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
        # a program's successor reads what it started: keep the grid in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="csr_candidate_topk",
    )(spans, rad, queries.astype(jnp.float32)[:, None, :],
      store.astype(jnp.float32))
    return outd[:, 0], outi[:, 0]
