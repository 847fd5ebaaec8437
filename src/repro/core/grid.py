"""GridIndex: the paper's "image" of the data set, built TPU-natively.

The paper rasterizes N points onto a G x G image whose pixels hold point
counts (one image per class for classification).  We keep that structure but
build it with sort-based bucketization (no serialized scatters):

  cell_id = quantize(project(x));  order = argsort(cell_id);
  offsets = searchsorted(cell_id[order], arange(G*G + 1))

which yields a CSR layout: points of cell c are `points_sorted[offsets[c] :
offsets[c + 1]]`.  Base-level counts are `diff(offsets)`; a count PYRAMID
(mip chain) on top gives O(1) circle counts at any radius (pyramid.py).

Everything here is a pytree of arrays; static knobs live in `GridConfig`
(frozen dataclass, passed as a static argument).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import integral as integral_lib
from repro.core import projection as proj_lib
from repro.core.projection import Projection


@dataclasses.dataclass(frozen=True)
class GridConfig:
    """Static configuration of a grid index (hashable; safe as a jit static arg)."""

    grid_size: int = 1024        # requested G (paper: 3000)
    tile: int = 16               # pyramid tile side T checked per count (VMEM-resident)
    n_classes: int = 0           # 0 = unlabeled (single count channel)
    window: int = 32             # candidate-gather window side (base cells)
    row_cap: int = 32            # max candidates gathered per window row
    r0: int = 100                # paper's initial radius (pixels)
    max_iters: int = 16          # Eq.-1 iteration cap
    k_slack: float = 1.0         # accept n in [k, k_slack * k]; 1.0 = paper-exact
    metric: str = "l2"           # "l2" | "l1" (paper discusses both)
    counter: str = "pyramid"     # "pyramid" | "sat" (exact L-inf counts, integral.py)

    def __post_init__(self):
        # level_for_radius picks the level where a T-cell window contains the
        # circle via 2**l >= 2r / (tile - 3); with tile <= 3 the (tile - 3)
        # margin vanishes and its max(tile - 3, 1) divisor would silently
        # break the containment guarantee — reject the config outright.
        if self.tile <= 3:
            raise ValueError(
                f"tile={self.tile} is too small: the pyramid window needs a "
                "positive containment margin (tile/2 - 1.5), so tile must "
                "be >= 4"
            )
        # _metric_dist and the count kernels treat ANY non-"l1" string as l2;
        # reject typos eagerly instead of silently computing l2 distances.
        if self.metric not in ("l2", "l1"):
            raise ValueError(
                f"unknown metric {self.metric!r}; expected 'l2' or 'l1'"
            )
        if self.counter not in ("pyramid", "sat"):
            raise ValueError(
                f"unknown counter {self.counter!r}; expected 'pyramid' or 'sat'"
            )
        # The radius loop used to jnp.clip(r0, 1, max_radius) silently, so a
        # typo'd r0 (0, negative, or wider than the countable max) ran with a
        # DIFFERENT start radius than configured.  Reject it here, like the
        # tile/metric/counter checks above.
        if self.r0 <= 0:
            raise ValueError(
                f"r0={self.r0} must be a positive start radius (pixels)"
            )
        if self.r0 > self.max_radius:
            raise ValueError(
                f"r0={self.r0} exceeds max_radius={self.max_radius} (the "
                f"largest radius countable from the top pyramid tile for "
                f"grid_size={self.grid_size}, tile={self.tile})"
            )

    @property
    def n_channels(self) -> int:
        return max(self.n_classes, 1)

    @property
    def levels(self) -> int:
        """Number of pyramid levels so the TOP level is exactly `tile` wide."""
        return max(1, math.ceil(math.log2(max(self.grid_size, self.tile) / self.tile)) + 1)

    @property
    def padded_size(self) -> int:
        """G padded so padded_size == tile * 2**(levels-1) (clean mip chain)."""
        return self.tile * (1 << (self.levels - 1))

    @property
    def max_radius(self) -> int:
        """Any radius up to this is countable from the top pyramid tile."""
        return self.padded_size

    @property
    def max_candidates(self) -> int:
        return self.window * self.row_cap

    @property
    def level_nblks(self) -> tuple[int, ...]:
        """Per-level T-block counts S_l // tile — static layout of the
        flattened tile array consumed by kernels.tile_count_multilevel."""
        return tuple(1 << (self.levels - 1 - l) for l in range(self.levels))


class GridIndex(NamedTuple):
    """The built index.  All arrays; shardable along the points axis (N)."""

    proj: Projection          # grid-space projection + extents
    points_sorted: jax.Array  # (N, d) float32 — original points, CSR order
    coords_sorted: jax.Array  # (N, 2) float32 — continuous grid coords, CSR order
    labels_sorted: jax.Array  # (N,) int32 — class label (or 0), CSR order
    ids_sorted: jax.Array     # (N,) int32 — original (or global) point index
    offsets: jax.Array        # (padded_size**2 + 1,) int32 CSR cell offsets
    pyramid: tuple[jax.Array, ...]  # level l: (S_l, S_l, C) int32, S_l = padded/2**l
    sat: jax.Array | None = None    # (S+1, S+1, C) summed-area table (counter="sat")
    pyr_tiles: jax.Array | None = None  # (sum_l nblk_l^2, C, T, T) int32 —
    # the pyramid pre-cut into T-aligned tiles and concatenated level-major
    # (flatten_pyramid_tiles); the level-scheduled count kernel's input
    global_offsets: jax.Array | None = None  # (padded_size**2 + 1,) int32 —
    # a shard of a sharded index only (core/distributed.py): the CSR offsets
    # of ALL shards' points, while `offsets` covers this shard's records and
    # pyramid/sat/pyr_tiles hold the counts of all shards
    global_cells: jax.Array | None = None  # (L,) int32, L a power of two
    # above the points of all shards — sharded only: the cell of the record
    # at each global CSR position, padded_size**2 past the last

    @property
    def n_points(self) -> int:
        return self.points_sorted.shape[0]


def cell_id_of(coords: jax.Array, padded_size: int) -> jax.Array:
    """Row-major flat cell id from continuous grid coords (..., 2)."""
    cell = jnp.floor(coords).astype(jnp.int32)
    return cell[..., 0] * padded_size + cell[..., 1]


def build_pyramid(base: jax.Array, levels: int) -> tuple[jax.Array, ...]:
    """Mip chain of count sums.  base: (S, S, C) int32, S = tile * 2**(levels-1)."""
    out = [base]
    cur = base
    for _ in range(levels - 1):
        s = cur.shape[0] // 2
        cur = cur.reshape(s, 2, s, 2, cur.shape[-1]).sum(axis=(1, 3))
        out.append(cur)
    return tuple(out)


def flatten_pyramid_tiles(pyramid: tuple[jax.Array, ...], tile: int) -> jax.Array:
    """Flatten a mip chain into one (sum_l nblk_l^2, C, T, T) tile array.

    Level l's (S_l, S_l, C) image becomes nblk_l^2 row-major channel-major
    (C, T, T) tiles (nblk_l = S_l // T); levels are concatenated in order,
    so tile (bx, by) of level l lives at row offset_l + bx * nblk_l + by.
    This is the DMA-friendly layout tile_count_multilevel block-indexes
    into: the (T, T) cell plane sits in the two minor dims that the TPU
    tiles, so a tile is not padded out to 128 lanes per cell.
    """
    blocks = []
    for arr in pyramid:
        s, _, c = arr.shape
        nb = s // tile
        blocks.append(
            arr.reshape(nb, tile, nb, tile, c)
            .transpose(0, 2, 4, 1, 3)
            .reshape(nb * nb, c, tile, tile)
        )
    return jnp.concatenate(blocks, axis=0)


def build_index(
    points: jax.Array,
    cfg: GridConfig,
    proj: Projection,
    labels: jax.Array | None = None,
    ids: jax.Array | None = None,
) -> GridIndex:
    """Build the paper's image + CSR buckets + count pyramid.  jit-able.

    `ids` lets a distributed shard record GLOBAL point indices (distributed.py).
    """
    n = points.shape[0]
    g = cfg.padded_size
    coords = proj_lib.to_grid_coords(proj, points, cfg.grid_size)  # in [0, grid_size)
    cid = cell_id_of(coords, g)

    order = jnp.argsort(cid)
    cid_sorted = cid[order]
    offsets = jnp.searchsorted(cid_sorted, jnp.arange(g * g + 1, dtype=jnp.int32)).astype(
        jnp.int32
    )

    if labels is None:
        labels = jnp.zeros((n,), dtype=jnp.int32)
    if ids is None:
        ids = jnp.arange(n, dtype=jnp.int32)

    c = cfg.n_channels
    base = jnp.zeros((g * g, c), dtype=jnp.int32)
    chan = jnp.where(cfg.n_classes > 0, labels, 0).astype(jnp.int32)
    base = base.at[cid, chan].add(1)
    base = base.reshape(g, g, c)
    pyramid = build_pyramid(base, cfg.levels)

    return GridIndex(
        proj=proj,
        points_sorted=points[order].astype(jnp.float32),
        coords_sorted=coords[order].astype(jnp.float32),
        labels_sorted=labels[order].astype(jnp.int32),
        ids_sorted=ids[order].astype(jnp.int32),
        offsets=offsets,
        pyramid=pyramid,
        sat=integral_lib.build_sat(base) if cfg.counter == "sat" else None,
        # only the pyramid counter's pallas path reads the flat tiling;
        # batched_counts treats None as a hard error (pre-layout indexes are
        # upgraded once by ActiveSearcher.from_index, never per call)
        pyr_tiles=(
            flatten_pyramid_tiles(pyramid, cfg.tile)
            if cfg.counter == "pyramid" else None
        ),
    )


def base_counts(index: GridIndex) -> jax.Array:
    """(S, S) total base-level counts (sum over class channels)."""
    return index.pyramid[0].sum(axis=-1)


def validate_invariants(index: GridIndex, cfg: GridConfig) -> dict[str, bool]:
    """Cheap structural invariants (used by property tests, and by the
    mutable-index suite on delta-updated snapshots)."""
    n = index.n_points
    offs = index.offsets
    counts_from_offsets = offs[-1] == n
    monotone = bool(jnp.all(offs[1:] >= offs[:-1]))
    pyramid_mass = all(int(level.sum()) == n for level in index.pyramid)
    cid = cell_id_of(index.coords_sorted, cfg.padded_size)
    sorted_ok = bool(jnp.all(cid[1:] >= cid[:-1]))
    # base level agrees with the CSR bucket sizes, and every coarser level is
    # exactly the 2x2 sum of the level below it (delta updates must keep the
    # whole mip chain consistent, not just the base)
    base_ok = bool(
        jnp.all(index.pyramid[0].sum(axis=-1).reshape(-1) == offs[1:] - offs[:-1])
    )
    chain_ok = all(
        bool(jnp.all(build_pyramid(index.pyramid[lv], 2)[1] == index.pyramid[lv + 1]))
        for lv in range(len(index.pyramid) - 1)
    )
    tiles_ok = (
        index.pyr_tiles is None
        or bool(
            jnp.all(index.pyr_tiles == flatten_pyramid_tiles(index.pyramid, cfg.tile))
        )
    )
    return {
        "offsets_end_is_n": bool(counts_from_offsets),
        "offsets_monotone": monotone,
        "pyramid_mass_is_n": pyramid_mass,
        "cells_sorted": sorted_ok,
        "base_matches_offsets": base_ok,
        "pyramid_chain_consistent": chain_ok,
        "tiles_match_pyramid": tiles_ok,
    }
