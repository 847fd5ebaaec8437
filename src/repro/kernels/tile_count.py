"""Pallas TPU kernel: circle-masked tile count (the paper's hot loop).

The paper's per-iteration cost is "checking all the inner pixels of the
current circle" (§3).  On TPU that becomes: DMA ONE fixed-size window of a
pyramid level from HBM into VMEM, apply the circular mask against cell
centers on the VPU, and reduce.  The window is data-dependent (it saccades to
the query), which we express with scalar-prefetched block origins driving the
BlockSpec index_map: the same level array is passed four times with index
maps (bx0+di, by0+dj), di,dj in {0,1}, so the four T-aligned tiles cover any
un-aligned T-window.

Layout notes for the v5e target: the level is passed channel-major
(C, S, S), so each tile is a (C, T, T) block whose (T, T) cell plane sits in
the two minor dims the TPU tiles; T should be a multiple of 8 (sublanes).
Tested in interpret mode against ref.tile_count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def circle_window_sum(
    vals,   # (C, T, T) int32 — one cover tile's counts, channel-major
    bx, by,  # int32 — the tile's block coords (level-cell index / T)
    qx, qy, r, scale,  # query position, radius (base px), 2**level
    oxf, oyf,  # float32 — clamped window origin in level cells
    zero,   # bool — duplicate-cover tile, contribute nothing
    *,
    tile: int,
    metric: str,
):
    """(1, C) per-class sums of `vals` over cells inside the circle AND the
    clamped [ox, ox+T) x [oy, oy+T) reference window.

    The single shared definition of the counting contract (both count
    kernels call it), bit-for-bit with `pyramid._count_at_level`: the
    window mask keeps circles that overrun the window from reaching cells
    the oracle never scans, and `zero` blanks aliased duplicate tiles of
    the 2x2 block cover.  `scale` may be a static int (single-level) or a
    prefetched float32 scalar (level-scheduled).
    """
    # Mosaic has integer iotas only; small integers are exact in float32
    ii = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0).astype(jnp.float32)
    jj = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1).astype(jnp.float32)
    tf = jnp.float32(tile)
    gx = (bx * tile).astype(jnp.float32) + ii  # global level-cell index
    gy = (by * tile).astype(jnp.float32) + jj
    ci = (gx + 0.5) * scale                    # cell center, base px
    cj = (gy + 0.5) * scale
    if metric == "l1":
        inside = (jnp.abs(ci - qx) + jnp.abs(cj - qy)) <= r
    else:
        inside = (ci - qx) ** 2 + (cj - qy) ** 2 <= r * r
    window = (gx >= oxf) & (gx < oxf + tf) & (gy >= oyf) & (gy < oyf + tf)
    inside = jnp.logical_and(inside & window, jnp.logical_not(zero))
    mask = inside.astype(jnp.int32)
    # one (T, T) reduction per class, gathered into a (1, C) lane vector
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, vals.shape[0]), 1)
    out = jnp.zeros((1, vals.shape[0]), jnp.int32)
    for c in range(vals.shape[0]):
        out = jnp.where(lane == c, jnp.sum(vals[c] * mask), out)
    return out


def _kernel(
    origins_ref,  # scalar prefetch: (B, 4) int32 (bx0, by0, ox, oy) —
                  # block origins + clamped window origin in level cells
    q_ref,        # scalar prefetch: (B, 2) float32 query positions (base px)
    r_ref,        # scalar prefetch: (B,) float32 radii (base px)
    t00, t01, t10, t11,  # (C, T, T) int32 tiles
    out_ref,      # (1, C) int32 — block of the (B, 1, C) output
    *,
    tile: int,
    scale: int,
    nblk: int,
    metric: str,
):
    b = pl.program_id(0)
    bx0 = origins_ref[b, 0]
    by0 = origins_ref[b, 1]
    oxf = origins_ref[b, 2].astype(jnp.float32)
    oyf = origins_ref[b, 3].astype(jnp.float32)
    qx = q_ref[b, 0]
    qy = q_ref[b, 1]
    r = r_ref[b]

    # duplicate-tile guards: when bx0+1 is clamped by the index_map the
    # di=1 tiles alias the di=0 tiles and must contribute zero.
    dup_x = (bx0 + 1) > (nblk - 1)
    dup_y = (by0 + 1) > (nblk - 1)

    def masked_sum(t_ref, bx, by, zero):
        return circle_window_sum(
            t_ref[...], bx, by, qx, qy, r, scale, oxf, oyf, zero,
            tile=tile, metric=metric,
        )

    bx1 = jnp.minimum(bx0 + 1, nblk - 1)
    by1 = jnp.minimum(by0 + 1, nblk - 1)
    total = (
        masked_sum(t00, bx0, by0, False)
        + masked_sum(t01, bx0, by1, dup_y)
        + masked_sum(t10, bx1, by0, dup_x)
        + masked_sum(t11, bx1, by1, jnp.logical_or(dup_x, dup_y))
    )
    out_ref[...] = total


@functools.partial(
    jax.jit, static_argnames=("scale", "tile", "metric", "interpret")
)
def tile_count(
    level_arr: jax.Array,
    queries: jax.Array,
    radii: jax.Array,
    scale: int,
    tile: int,
    metric: str = "l2",
    *,
    interpret: bool,
) -> jax.Array:
    """Circle-masked counts (B, C) from one pyramid level (S, S, C).

    Contract identical to ref.tile_count (which mirrors
    pyramid._count_at_level) for EVERY radius: cells outside the clamped
    [ox, ox+T) x [oy, oy+T) reference window are masked out, so the kernel
    stays bit-for-bit with the oracle even when the circle overruns the
    window (radius clamped at the top level, grid-edge queries).
    """
    s, _, c = level_arr.shape
    if s % tile:
        raise ValueError(f"level size {s} must be a multiple of tile {tile}")
    nblk = s // tile
    b = queries.shape[0]

    q = queries.astype(jnp.float32)
    r = radii.astype(jnp.float32)
    cx = jnp.floor(q[:, 0] / scale).astype(jnp.int32)
    cy = jnp.floor(q[:, 1] / scale).astype(jnp.int32)
    ox = jnp.clip(cx - tile // 2, 0, s - tile)
    oy = jnp.clip(cy - tile // 2, 0, s - tile)
    # (B, 4): T-aligned block origin (drives the index_map) + exact window
    # origin (drives the in-kernel window-parity mask)
    origins = jnp.stack([ox // tile, oy // tile, ox, oy], axis=1)

    def im(di, dj):
        def index_map(i, origins_ref, q_ref, r_ref):
            bx = jnp.minimum(origins_ref[i, 0] + di, nblk - 1)
            by = jnp.minimum(origins_ref[i, 1] + dj, nblk - 1)
            return 0, bx, by

        return index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((c, tile, tile), im(0, 0)),
            pl.BlockSpec((c, tile, tile), im(0, 1)),
            pl.BlockSpec((c, tile, tile), im(1, 0)),
            pl.BlockSpec((c, tile, tile), im(1, 1)),
        ],
        out_specs=pl.BlockSpec((None, 1, c), lambda i, *_: (i, 0, 0)),
    )
    kernel = functools.partial(
        _kernel, tile=tile, scale=scale, nblk=nblk, metric=metric
    )
    chw = jnp.transpose(level_arr, (2, 0, 1))  # the kernel's (C, T, T) tiles
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.int32),
        interpret=interpret,
    )(origins, q, r, chw, chw, chw, chw)[:, 0]
