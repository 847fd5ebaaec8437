"""Pallas TPU kernel: level-scheduled circle count over the WHOLE pyramid.

The paper's "zoom" is level selection: each Eq.-1 iteration touches ONE
pyramid level per query.  `tile_count` (single-level) forced the batched
radius loop to run L stacked passes — every level for every query — and
select afterwards, an L-fold overcount.  This kernel schedules the level
INSIDE the pallas_call: the pyramid is passed as one flattened tile array
(sum_l nblk_l^2, C, T, T) — every level pre-cut into T-aligned (C, T, T)
tiles, concatenated along the leading axis — and each query's four cover
tiles are addressed by scalar-prefetched FLAT tile ids, so a single grid
program DMAs its window from the correct level.  Per-level scale is folded
into the prefetched geometry (a per-query float), not a static parameter.

Counting contract is `pyramid._count_at_level` at the query's level,
bit-for-bit for every radius: the circle mask is intersected with the
clamped [ox, ox+T) x [oy, oy+T) reference window (same window-parity rule
as tile_count), so overrunning circles never reach cells the oracle does
not scan.

Layout notes for the v5e target: one program touches 4 (C, T, T) int32
tiles + a (1, C) block of the (B, 1, C) output — with T=16..128, C<=8 this
stays far under VMEM, and VMEM use is independent of both L and B (B only
widens the grid), which is what lets serve-scale batches stream through
fixed-size invocations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_count import circle_window_sum


def level_tile_offsets(nblks: tuple[int, ...]) -> tuple[int, ...]:
    """Start row of each level in the flattened tile array (static)."""
    offs, acc = [], 0
    for nb in nblks:
        offs.append(acc)
        acc += nb * nb
    return tuple(offs)


def _kernel(
    tid_ref,    # scalar prefetch: (B, 4) int32 flat tile ids of the 2x2 cover
    geom_ref,   # scalar prefetch: (B, 9) int32
                #   (bx0, by0, bx1, by1, ox, oy, dup_x, dup_y, live)
                #   in level cells; live=0 marks a parked (masked-out) lane
    q_ref,      # scalar prefetch: (B, 2) float32 query positions (base px)
    rs_ref,     # scalar prefetch: (B, 2) float32 (radius, 2**level)
    t00, t01, t10, t11,  # (1, C, T, T) int32 tiles (level-scheduled via tid)
    out_ref,    # (1, C) int32 — block of the (B, 1, C) output
    *,
    tile: int,
    metric: str,
):
    b = pl.program_id(0)
    bx0 = geom_ref[b, 0]
    by0 = geom_ref[b, 1]
    bx1 = geom_ref[b, 2]
    by1 = geom_ref[b, 3]
    oxf = geom_ref[b, 4].astype(jnp.float32)
    oyf = geom_ref[b, 5].astype(jnp.float32)
    dup_x = geom_ref[b, 6] != 0
    dup_y = geom_ref[b, 7] != 0
    live = geom_ref[b, 8] != 0
    qx = q_ref[b, 0]
    qy = q_ref[b, 1]
    r = rs_ref[b, 0]
    scale = rs_ref[b, 1]

    def masked_sum(t_ref, bx, by, zero):
        return circle_window_sum(
            t_ref[0], bx, by, qx, qy, r, scale, oxf, oyf, zero,
            tile=tile, metric=metric,
        )

    total = (
        masked_sum(t00, bx0, by0, False)
        + masked_sum(t01, bx0, by1, dup_y)
        + masked_sum(t10, bx1, by0, dup_x)
        + masked_sum(t11, bx1, by1, jnp.logical_or(dup_x, dup_y))
    )
    # parked lanes alias the anchor lane's tiles (their DMAs were elided by
    # the revisiting rule) — their geometry is stale, so blank the output
    out_ref[...] = jnp.where(live, total, 0)


@functools.partial(
    jax.jit, static_argnames=("tile", "nblks", "metric", "interpret")
)
def tile_count_multilevel(
    tiles: jax.Array,       # (sum_l nblk_l^2, C, T, T) int32 flattened pyramid
    queries: jax.Array,     # (B, 2) float32, base-pixel units
    radii: jax.Array,       # (B,) float32, base-pixel units
    levels: jax.Array,      # (B,) int32 pyramid level per query
    tile: int,
    nblks: tuple[int, ...],  # per-level block counts S_l // T (static)
    metric: str = "l2",
    *,
    interpret: bool,
    active: jax.Array | None = None,  # (B,) bool lane mask (None = all live)
) -> jax.Array:
    """Level-scheduled circle counts (B, C) in ONE pallas_call.

    Equivalent to running tile_count at each query's own level (the stacked
    (L, B, C) select), but each grid program reads only its level's window.
    See grid.flatten_pyramid_tiles for the `tiles` layout.

    `active` masks lanes OUT of the count (converged Eq.-1 lanes whose state
    is frozen by the caller): live lanes are compacted toward a dense grid
    prefix (stable argsort on the mask) and every parked lane's prefetched
    tile ids are aliased to the LAST live lane's — consecutive grid programs
    whose BlockSpec index_map resolves to the same blocks reuse the already-
    resident buffers, so the pipeline never re-issues the parked lanes' tile
    DMAs.  Parked programs write zeros (their `live` geometry flag is 0) and
    the result is scattered back to caller order, so rows of live lanes are
    bit-identical to the unmasked call and parked rows are 0.  The grid
    stays a static (B,) — only the DMA traffic shrinks with convergence.
    """
    nb_total = sum(nb * nb for nb in nblks)
    if tiles.ndim != 4 or tiles.shape[0] != nb_total or tiles.shape[2:] != (tile, tile):
        raise ValueError(
            f"tiles shape {tiles.shape} does not match nblks={nblks}, tile={tile}"
        )
    c = tiles.shape[1]
    b = queries.shape[0]
    n_levels = len(nblks)

    nblk_tab = jnp.asarray(nblks, jnp.int32)
    off_tab = jnp.asarray(level_tile_offsets(nblks), jnp.int32)

    lv = jnp.clip(levels.astype(jnp.int32), 0, n_levels - 1)   # (B,)
    nblk = nblk_tab[lv]
    base = off_tab[lv]
    scale = (jnp.int32(1) << lv).astype(jnp.float32)

    q = queries.astype(jnp.float32)
    r = radii.astype(jnp.float32)
    s_l = nblk * tile
    cx = jnp.floor(q[:, 0] / scale).astype(jnp.int32)
    cy = jnp.floor(q[:, 1] / scale).astype(jnp.int32)
    ox = jnp.clip(cx - tile // 2, 0, s_l - tile)
    oy = jnp.clip(cy - tile // 2, 0, s_l - tile)
    bx0 = ox // tile
    by0 = oy // tile
    dup_x = (bx0 + 1) > (nblk - 1)
    dup_y = (by0 + 1) > (nblk - 1)
    bx1 = jnp.minimum(bx0 + 1, nblk - 1)
    by1 = jnp.minimum(by0 + 1, nblk - 1)

    tid = jnp.stack(
        [
            base + bx0 * nblk + by0,
            base + bx0 * nblk + by1,
            base + bx1 * nblk + by0,
            base + bx1 * nblk + by1,
        ],
        axis=1,
    ).astype(jnp.int32)
    live = (
        jnp.ones((b,), jnp.int32) if active is None
        else active.astype(jnp.int32)
    )
    geom = jnp.stack(
        [bx0, by0, bx1, by1, ox, oy,
         dup_x.astype(jnp.int32), dup_y.astype(jnp.int32), live],
        axis=1,
    )
    rs = jnp.stack([r, scale], axis=1)

    inv = None
    if active is None:
        act = None
    else:
        act = active.astype(bool)
        # compact live lanes to a dense prefix (stable: live lanes keep their
        # relative order) and alias every parked lane's tile cover to the
        # last live lane's, so the tail of the grid revisits one resident
        # block set instead of DMAing per-lane tiles it will discard
        order = jnp.argsort(jnp.logical_not(act), stable=True)
        inv = jnp.argsort(order, stable=True)
        anchor = jnp.maximum(jnp.sum(act.astype(jnp.int32)) - 1, 0)
        tid, geom, q, rs = tid[order], geom[order], q[order], rs[order]
        tid = jnp.where(geom[:, 8:9] != 0, tid, tid[anchor][None, :])

    def im(t):
        def index_map(i, tid_ref, geom_ref, q_ref, rs_ref):
            return tid_ref[i, t], 0, 0, 0

        return index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, tile, tile), im(t)) for t in range(4)],
        # (B, 1, C) with the batch dim squeezed: a (1, C) block of a (B, C)
        # array would break the (8, 128) block rule
        out_specs=pl.BlockSpec((None, 1, c), lambda i, *_: (i, 0, 0)),
    )
    kernel = functools.partial(_kernel, tile=tile, metric=metric)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.int32),
        interpret=interpret,
        name="tile_count_multilevel",
    )(tid, geom, q, rs, tiles, tiles, tiles, tiles)[:, 0]
    if act is None:
        return out
    # back to caller order; parked rows pinned to 0 (the kernel already
    # blanked them, the where keeps the contract explicit)
    return jnp.where(act[:, None], out[inv], 0)
