"""Mutable grid index — streaming insert/delete as DELTA updates.

`build_index` produces a frozen snapshot: CSR buckets packed edge to edge,
pyramid summed from scratch, tiles flattened once.  Serving workloads (the
kNN-LM datastore growing during decode, retrieval positions appended token by
token) need the index to GROW without paying the O(N log N) rebuild, so this
module keeps the same structure in a mutable layout:

  * the CSR record arrays get per-cell SLACK — each bucket is allocated
    `capacity >= size` slots, so an insert into a bucket with free slots is
    one scatter per record field;
  * inserts that do not fit their bucket (full bucket, or a cell that was
    empty at layout time) go to a SPILL log, an append-only slab merged back
    into cell order by `snapshot()`/`compact()` with an O(N) order-preserving
    merge (no full argsort);
  * deletes tombstone their slot (`live=False`) — bucket order is preserved,
    the slot is reclaimed at the next `compact()`;
  * the count pyramid is maintained exactly by scatter-adding +/-1 at every
    level for each touched cell (integer adds, so the result is bit-identical
    to a from-scratch `build_pyramid`), and only the DIRTY T-tiles of the
    flattened `pyr_tiles` layout are re-gathered;
  * when the spill log itself overflows, `insert` takes the escape hatch:
    `compact()` (re-layout with fresh slack; order-preserving, no sort) by
    default, or raises `BucketOverflow` with `on_overflow="raise"`.

The headline invariant (tests/test_mutable.py): for any split P = P1 ∪ P2,

    snapshot(insert(from_index(build_index(P1)), P2)) == build_index(P)

bit for bit — same CSR order (stable argsort puts same-cell points in
arrival order; buckets + spill reproduce exactly that), same offsets, same
pyramid, same flattened tiles — so every registered search backend returns
identical results on the incrementally built index.

Facade surface: `ActiveSearcher.insert/.delete/.snapshot()` (core/engine.py)
carry a `MutableIndex` alongside the dense snapshot; `retrieval_memory` and
`knn_lm` expose `extend_*` helpers on top of it; `checkpoint/store.py`
persists the state via `state_to_tree`/`state_from_tree`.
"""

from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projection as proj_lib
from repro.core.grid import (
    GridConfig,
    GridIndex,
    build_index,
    cell_id_of,
    flatten_pyramid_tiles,
)
from repro.core.projection import Projection


class BucketOverflow(RuntimeError):
    """An insert did not fit the bucket slack and the spill log is full.

    Raised only with `on_overflow="raise"`; the default policy compacts the
    layout (fresh slack, spill merged back into buckets) and retries.
    """


class Slab(NamedTuple):
    """One block of CSR slot storage (the bucketed base, or the spill log).

    Dead/free slots carry `ids == -1`, `cell == -1`, `live == False`.
    """

    points: jax.Array  # (cap, d) float32
    coords: jax.Array  # (cap, 2) float32
    labels: jax.Array  # (cap,) int32
    ids: jax.Array     # (cap,) int32
    cell: jax.Array    # (cap,) int32 — flat base cell id of the slot's record
    live: jax.Array    # (cap,) bool


class MutableIndex(NamedTuple):
    """A grid index open for streaming mutation.  All-array pytree.

    `base` holds the bucketed records: bucket c occupies slots
    [cap_offsets[c], cap_offsets[c+1]); the first `used[c]` slots of the
    bucket have been handed out (some may be tombstoned), the rest are free.
    `spill` is the append-only overflow log in ARRIVAL order; `spilled[c]`
    pins a cell to the spill log once any of its inserts spilled, so bucket
    slots never receive records that must sort AFTER spilled ones.
    """

    proj: Projection
    base: Slab
    spill: Slab
    cap_offsets: jax.Array  # (G*G + 1,) int32 bucket capacity prefix sum
    used: jax.Array         # (G*G,) int32 slots handed out per bucket
    spilled: jax.Array      # (G*G,) bool — cell routes to the spill log
    spill_used: jax.Array   # () int32 — occupied prefix of the spill slab
    pyramid: tuple[jax.Array, ...]
    pyr_tiles: jax.Array | None
    next_id: jax.Array      # () int32 — next auto-assigned global id
    n_live: jax.Array       # () int32 — live records (base + spill)

    @property
    def spill_capacity(self) -> int:
        return self.spill.ids.shape[0]

    @property
    def free_bucket_slots(self) -> jax.Array:
        """() int32 — total unallocated bucket slots across all cells."""
        caps = self.cap_offsets[1:] - self.cap_offsets[:-1]
        return jnp.sum(caps - self.used)


# ------------------------------------------------------------ construction ---


def _empty_slab(cap: int, d: int) -> Slab:
    return Slab(
        points=jnp.zeros((cap, d), jnp.float32),
        coords=jnp.zeros((cap, 2), jnp.float32),
        labels=jnp.zeros((cap,), jnp.int32),
        ids=jnp.full((cap,), -1, jnp.int32),
        cell=jnp.full((cap,), -1, jnp.int32),
        live=jnp.zeros((cap,), bool),
    )


def _scatter_slab(slab: Slab, pos: jax.Array, keep: jax.Array, *,
                  points, coords, labels, ids, cell) -> Slab:
    """Write records into `slab` at `pos` where `keep`; dropped elsewhere."""
    cap = slab.ids.shape[0]
    idx = jnp.where(keep, pos, cap)  # out-of-range rows drop
    return Slab(
        points=slab.points.at[idx].set(points, mode="drop"),
        coords=slab.coords.at[idx].set(coords, mode="drop"),
        labels=slab.labels.at[idx].set(labels, mode="drop"),
        ids=slab.ids.at[idx].set(ids, mode="drop"),
        cell=slab.cell.at[idx].set(cell, mode="drop"),
        live=slab.live.at[idx].set(True, mode="drop"),
    )


@partial(jax.jit, static_argnames=("g", "total_cap", "d"))
def _layout_base(index: GridIndex, cap_offsets, g: int, total_cap: int, d: int):
    n = index.points_sorted.shape[0]
    cell = cell_id_of(index.coords_sorted, g)                       # (N,)
    # CSR rank within the cell -> bucket slot
    pos = cap_offsets[cell] + (jnp.arange(n, dtype=jnp.int32) - index.offsets[cell])
    return _scatter_slab(
        _empty_slab(total_cap, d), pos, jnp.ones((n,), bool),
        points=index.points_sorted, coords=index.coords_sorted,
        labels=index.labels_sorted, ids=index.ids_sorted, cell=cell,
    )


def from_index(
    index: GridIndex,
    cfg: GridConfig,
    slack: float = 0.5,
    min_slack: int = 4,
    spill_capacity: int | None = None,
    next_id: int | None = None,
) -> MutableIndex:
    """Open a built `GridIndex` for mutation.

    Bucket capacity is `size + max(ceil(slack * size), min_slack)` for
    non-empty cells (empty cells get no slots — their inserts spill).  The
    layout pass is O(N) scatters; no sort.
    """
    g = cfg.padded_size
    n = index.n_points
    d = index.points_sorted.shape[1]

    sizes = index.offsets[1:] - index.offsets[:-1]                  # (G*G,)
    extra = jnp.maximum(
        jnp.ceil(sizes.astype(jnp.float32) * slack).astype(jnp.int32),
        jnp.int32(min_slack),
    )
    caps = jnp.where(sizes > 0, sizes + extra, 0)
    cap_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(caps).astype(jnp.int32)]
    )
    total_cap = int(cap_offsets[-1])
    base = _layout_base(index, cap_offsets, g, total_cap, d)

    if spill_capacity is None:
        spill_capacity = max(1024, n // 4)
    tiles = index.pyr_tiles
    if tiles is None and cfg.counter == "pyramid":
        tiles = flatten_pyramid_tiles(index.pyramid, cfg.tile)
    if next_id is None:
        next_id = int(index.ids_sorted.max()) + 1 if n else 0
    return MutableIndex(
        proj=index.proj,
        base=base,
        spill=_empty_slab(spill_capacity, d),
        cap_offsets=cap_offsets,
        used=sizes,
        spilled=jnp.zeros((g * g,), bool),
        spill_used=jnp.int32(0),
        pyramid=index.pyramid,
        pyr_tiles=tiles,
        next_id=jnp.int32(next_id),
        n_live=jnp.int32(n),
    )


# ------------------------------------------------------------ delta helpers --


def _pyramid_delta(
    pyramid: tuple[jax.Array, ...], cx, cy, chan, amount
) -> tuple[jax.Array, ...]:
    """Scatter `amount` per (cell, channel) into EVERY level (exact int
    adds; amount may be a per-entry array, so padding entries can add 0)."""
    out = []
    for lv, arr in enumerate(pyramid):
        out.append(arr.at[cx >> lv, cy >> lv, chan].add(amount))
    return tuple(out)


def _pad_pow2(arr: np.ndarray, fill) -> np.ndarray:
    """Pad a 1-D host array to the next power-of-two length (bounds the
    number of distinct shapes the jitted delta kernels compile for)."""
    n = len(arr)
    cap = 1 << max(n - 1, 0).bit_length()
    return np.concatenate([arr, np.full((cap - n,), fill, arr.dtype)])


def _dirty_tile_rows(cfg: GridConfig, cx, cy) -> list[np.ndarray]:
    """Per level, the UNIQUE flat `pyr_tiles` rows covering the given cells."""
    t = cfg.tile
    rows = []
    for lv, nblk in enumerate(cfg.level_nblks):
        bx = np.asarray(cx >> lv) // t
        by = np.asarray(cy >> lv) // t
        rows.append(np.unique(bx * nblk + by).astype(np.int32))
    return rows


@partial(jax.jit, static_argnames=("t", "nblk", "offset"))
def _update_tiles_level(pyr_tiles, level_arr, local, t: int, nblk: int, offset: int):
    """Re-gather the given flat tile rows of ONE level from its (already
    delta-updated) image.  `local` may contain duplicates (pow2 padding
    repeats a row); duplicate rows re-write identical fresh content."""
    bx, by = local // nblk, local % nblk
    fresh = jax.vmap(
        lambda x, y: jax.lax.dynamic_slice(
            level_arr, (x * t, y * t, 0), (t, t, level_arr.shape[-1])
        ).transpose(2, 0, 1)
    )(bx, by)
    return pyr_tiles.at[local + offset].set(fresh, unique_indices=False)


_flatten_tiles_jit = jax.jit(flatten_pyramid_tiles, static_argnames=("tile",))


def _refresh_tiles(
    pyr_tiles: jax.Array | None,
    pyramid: tuple[jax.Array, ...],
    cfg: GridConfig,
    cx,
    cy,
) -> jax.Array | None:
    """Re-flatten ONLY the T-tiles whose counts changed.

    Each dirty row is re-gathered from its (already delta-updated) pyramid
    level with one dynamic_slice — O(dirty * T^2) instead of O(sum_l S_l^2).
    Falls back to a full `flatten_pyramid_tiles` when most rows are dirty.
    """
    if pyr_tiles is None:
        return None
    t = cfg.tile
    per_level = _dirty_tile_rows(cfg, cx, cy)
    n_dirty = sum(len(r) for r in per_level)
    if n_dirty * 4 >= pyr_tiles.shape[0]:
        return _flatten_tiles_jit(pyramid, tile=t)

    offset = 0
    for lv, nblk in enumerate(cfg.level_nblks):
        local = per_level[lv]
        if len(local):
            # pad by repeating the first dirty row: idempotent re-write
            padded = jnp.asarray(_pad_pow2(local, local[0]))
            pyr_tiles = _update_tiles_level(
                pyr_tiles, pyramid[lv], padded, t, nblk, offset
            )
        offset += nblk * nblk
    return pyr_tiles


def _chan_of(labels: jax.Array, cfg: GridConfig) -> jax.Array:
    return jnp.where(cfg.n_classes > 0, labels, 0).astype(jnp.int32)


# ----------------------------------------------------------------- insert ----


@partial(jax.jit, static_argnames=("cfg",))
def _plan_insert(m: MutableIndex, cfg: GridConfig, points, n_real):
    """coords/cell/arrival-rank/fits for a (pow2-padded) insert batch.

    Rows past `n_real` are padding: they get the sentinel cell G*G so they
    cannot perturb the arrival ranks of real cells, and `fits` is False for
    them (every downstream scatter drops on the keep/fits masks)."""
    g = cfg.padded_size
    mn = points.shape[0]
    keep = jnp.arange(mn, dtype=jnp.int32) < n_real
    coords = proj_lib.to_grid_coords(m.proj, points, cfg.grid_size)
    cid = jnp.where(keep, cell_id_of(coords, g), g * g)

    # arrival rank within each cell of THIS batch (stable sort by cell)
    order = jnp.argsort(cid, stable=True)
    sorted_cid = cid[order]
    rank_sorted = jnp.arange(mn, dtype=jnp.int32) - jnp.searchsorted(
        sorted_cid, sorted_cid, side="left"
    ).astype(jnp.int32)
    rank = jnp.zeros((mn,), jnp.int32).at[order].set(rank_sorted)

    caps = m.cap_offsets[1:] - m.cap_offsets[:-1]
    c = jnp.minimum(cid, g * g - 1)  # sentinel-safe gathers (masked by keep)
    fits = (~m.spilled[c]) & (m.used[c] + rank < caps[c]) & keep
    return coords, cid, rank, fits, keep


@partial(jax.jit, static_argnames=("cfg", "has_spill"))
def _apply_insert(
    m: MutableIndex, cfg: GridConfig, points, coords, cid, rank, fits, keep,
    labels, ids, has_spill: bool,
) -> MutableIndex:
    g = cfg.padded_size
    # sentinel rows index used[] out of bounds (gather clamps) — harmless,
    # their fits is False so the scatter drops them
    base = _scatter_slab(
        m.base, m.cap_offsets[cid] + m.used[jnp.minimum(cid, g * g - 1)] + rank,
        fits,
        points=points, coords=coords, labels=labels, ids=ids, cell=cid,
    )
    used = m.used.at[jnp.where(fits, cid, g * g)].add(1, mode="drop")

    spill, spilled, spill_used = m.spill, m.spilled, m.spill_used
    sp = (~fits) & keep
    if has_spill:
        # spill keeps ARRIVAL order: rank the non-fitting points by batch pos
        sp_rank = jnp.cumsum(sp.astype(jnp.int32)) - 1
        spill = _scatter_slab(
            spill, m.spill_used + sp_rank, sp,
            points=points, coords=coords, labels=labels, ids=ids, cell=cid,
        )
        spilled = spilled.at[jnp.where(sp, cid, g * g)].set(True, mode="drop")
        spill_used = m.spill_used + jnp.sum(sp.astype(jnp.int32))

    # padding rows land on the sentinel cell (cx == g, dropped out of
    # bounds) with amount 0 — doubly inert
    pyramid = _pyramid_delta(
        m.pyramid, cid // g, cid % g, _chan_of(labels, cfg),
        keep.astype(jnp.int32),
    )
    return m._replace(
        base=base,
        spill=spill,
        used=used,
        spilled=spilled,
        spill_used=spill_used,
        pyramid=pyramid,
        next_id=jnp.maximum(m.next_id, ids.max() + 1),
        n_live=m.n_live + jnp.sum(keep.astype(jnp.int32)),
    )


def insert(
    m: MutableIndex,
    cfg: GridConfig,
    points: jax.Array,
    labels: jax.Array | None = None,
    ids: jax.Array | None = None,
    on_overflow: str = "compact",
) -> MutableIndex:
    """Insert a batch of points; returns a NEW state (m is unchanged).

    Each point lands in its bucket's next free slot when one exists (and the
    cell has never spilled); otherwise it appends to the spill log.  The
    pyramid and dirty tiles are delta-updated either way, so counts are
    always current — only `snapshot()` pays the (sort-free) merge.

    on_overflow: "compact" re-layouts with fresh slack and retries when the
    spill log is full; "raise" raises `BucketOverflow` instead.

    Caller-supplied `ids` should be globally unique and not collide with
    live ids — records are keyed by id, so delete(id) removes EVERY record
    carrying it.  Auto-assigned ids (ids=None) never collide.
    """
    if on_overflow not in ("compact", "raise"):
        raise ValueError(
            f"unknown on_overflow {on_overflow!r}; expected 'compact' or 'raise'"
        )
    points = jnp.asarray(points, jnp.float32)
    mn = points.shape[0]
    if mn == 0:
        return m
    if labels is None:
        labels = jnp.zeros((mn,), jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    if ids is None:
        ids = m.next_id + jnp.arange(mn, dtype=jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)

    # pow2-pad the batch (sentinel cell, keep=False, id=-1) so the jitted
    # insert kernels compile for O(log batch) distinct shapes, matching the
    # bounded-compile design of delete()
    cap = 1 << max(mn - 1, 0).bit_length()
    if cap != mn:
        pad = cap - mn
        points_p = jnp.concatenate(
            [points, jnp.broadcast_to(points[-1:], (pad,) + points.shape[1:])]
        )
        labels_p = jnp.concatenate([labels, jnp.zeros((pad,), jnp.int32)])
        ids_p = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)])
    else:
        points_p, labels_p, ids_p = points, labels, ids

    coords, cid, rank, fits, keep = _plan_insert(
        m, cfg, points_p, jnp.int32(mn)
    )

    n_spill = int(jnp.sum((~fits) & keep))
    if n_spill and int(m.spill_used) + n_spill > m.spill_capacity:
        if on_overflow == "raise":
            raise BucketOverflow(
                f"insert of {mn} points needs {n_spill} spill slots but only "
                f"{m.spill_capacity - int(m.spill_used)} remain; "
                f"compact() or rebuild() the index"
            )
        # compact() re-tightens bucket slack, so points that fit THIS layout
        # may spill in the fresh one — only capacity >= the whole batch
        # guarantees the retry cannot overflow the (now empty) spill log
        grow = max(2 * m.spill_capacity, mn)
        m = compact(m, cfg, spill_capacity=grow)
        return insert(m, cfg, points, labels, ids, on_overflow="raise")

    out = _apply_insert(
        m, cfg, points_p, coords, cid, rank, fits, keep, labels_p, ids_p,
        has_spill=n_spill > 0,
    )
    real_cid = cid[:mn]  # padding rows map past the last level's tile rows
    tiles = _refresh_tiles(m.pyr_tiles, out.pyramid, cfg,
                           real_cid // cfg.padded_size,
                           real_cid % cfg.padded_size)
    return out._replace(pyr_tiles=tiles)


class InsertReport(NamedTuple):
    """What `insert_tracked` did BESIDES the insert: overflow compactions and
    the wall-clock pause they cost — the serving tier's backpressure signal
    (BENCH_serve.json reports both)."""

    compactions: int
    compact_s: float


def insert_tracked(
    m: MutableIndex,
    cfg: GridConfig,
    points: jax.Array,
    labels: jax.Array | None = None,
    ids: jax.Array | None = None,
) -> tuple[MutableIndex, InsertReport]:
    """`insert` with EXPLICIT, shard-local overflow handling.

    On `BucketOverflow` this compacts THIS state only and retries — in a
    sharded tier (core/distributed.py) sibling shards keep their exact state
    objects, so one full shard never stalls the others.  The retry's spill
    capacity covers the whole batch (same rule as `insert`'s internal escape
    hatch), so it cannot overflow again.  Returns (new_state, report); the
    report carries the compaction count (0 or 1) and the blocking pause in
    seconds."""
    try:
        out = insert(m, cfg, points, labels=labels, ids=ids,
                     on_overflow="raise")
        return out, InsertReport(compactions=0, compact_s=0.0)
    except BucketOverflow:
        t0 = time.perf_counter()
        mn = int(jnp.asarray(points).shape[0])
        grow = max(2 * m.spill_capacity, mn)
        packed = compact(m, cfg, spill_capacity=grow)
        out = insert(packed, cfg, points, labels=labels, ids=ids,
                     on_overflow="raise")
        jax.block_until_ready(out.base.ids)
        return out, InsertReport(
            compactions=1, compact_s=time.perf_counter() - t0
        )


# ----------------------------------------------------------------- delete ----


def delete(
    m: MutableIndex, cfg: GridConfig, ids: jax.Array, strict: bool = True
) -> MutableIndex:
    """Tombstone the records with the given global ids; returns a NEW state.

    Bucket order is untouched (the slot just goes dead), so a later
    `snapshot()` reproduces exactly the CSR order of rebuilding from the
    surviving points.  With strict=True (default) every id must name a live
    record; strict=False ignores unknown ids.
    """
    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    if ids.shape[0] == 0:
        return m
    kill_base, kill_spill = _plan_delete(m, ids)
    n_kill = int(jnp.sum(kill_base)) + int(jnp.sum(kill_spill))

    g = cfg.padded_size
    # device-side nonzero + gathers: only O(n_kill) records cross to the
    # host (for pow2 padding), never the full slab arrays
    idx_b = jnp.nonzero(kill_base)[0]
    idx_s = jnp.nonzero(kill_spill)[0]
    dead_ids = np.asarray(
        jnp.concatenate([m.base.ids[idx_b], m.spill.ids[idx_s]])
    )
    # count matched IDS, not slots: duplicate ids (caller-supplied id
    # collisions) kill every carrier, which must not read as "id not live"
    n_asked = int(jnp.unique(ids).shape[0])
    n_matched = len(np.unique(dead_ids))
    if strict and n_matched != n_asked:
        raise KeyError(
            f"delete: {n_asked - n_matched} of {n_asked} ids are not live in "
            f"the index (already deleted, or never inserted)"
        )
    dead_cell = np.asarray(
        jnp.concatenate([m.base.cell[idx_b], m.spill.cell[idx_s]])
    ).astype(np.int32)
    dead_lab = np.asarray(
        jnp.concatenate([m.base.labels[idx_b], m.spill.labels[idx_s]])
    ).astype(np.int32)
    # pow2 padding (cell 0, amount 0) keeps the jitted delta shape-stable
    amount = _pad_pow2(np.full((n_kill,), -1, np.int32), 0)
    dead_cell = jnp.asarray(_pad_pow2(dead_cell, 0))
    dead_lab = jnp.asarray(_pad_pow2(dead_lab, 0))

    out = _apply_delete(m, cfg, kill_base, kill_spill, dead_cell, dead_lab,
                        jnp.asarray(amount), jnp.int32(n_kill))
    tiles = _refresh_tiles(m.pyr_tiles, out.pyramid, cfg,
                           dead_cell // g, dead_cell % g)
    return out._replace(pyr_tiles=tiles)


@jax.jit
def _plan_delete(m: MutableIndex, ids):
    kill_base = jnp.isin(m.base.ids, ids) & m.base.live
    in_spill = jnp.arange(m.spill.ids.shape[0]) < m.spill_used
    kill_spill = jnp.isin(m.spill.ids, ids) & m.spill.live & in_spill
    return kill_base, kill_spill


@jax.jit
def ids_live_mask(m: MutableIndex, ids: jax.Array) -> jax.Array:
    """(len(ids),) bool — which of `ids` name at least one LIVE record here.

    The sharded delete router (distributed.sharded_delete) asks every shard
    this question to do GLOBAL strict accounting before issuing per-shard
    lenient deletes.  Dead/free slots are masked to -2 (never a caller id;
    -1 is the free-slot sentinel a caller could conceivably pass)."""
    base_ids = jnp.where(m.base.live, m.base.ids, -2)
    in_spill = jnp.arange(m.spill.ids.shape[0]) < m.spill_used
    spill_ids = jnp.where(m.spill.live & in_spill, m.spill.ids, -2)
    return jnp.isin(ids, base_ids) | jnp.isin(ids, spill_ids)


@partial(jax.jit, static_argnames=("cfg",))
def _apply_delete(
    m: MutableIndex, cfg: GridConfig, kill_base, kill_spill,
    dead_cell, dead_lab, amount, n_kill,
) -> MutableIndex:
    g = cfg.padded_size
    pyramid = _pyramid_delta(
        m.pyramid, dead_cell // g, dead_cell % g,
        _chan_of(dead_lab, cfg), amount,
    )
    return m._replace(
        base=m.base._replace(live=m.base.live & ~kill_base),
        spill=m.spill._replace(live=m.spill.live & ~kill_spill),
        pyramid=pyramid,
        n_live=m.n_live - jnp.int32(n_kill),
    )


# --------------------------------------------------------------- snapshot ----


@partial(jax.jit, static_argnames=("cfg",))
def _merge_snapshot(m: MutableIndex, cfg: GridConfig):
    """The snapshot merge at FULL slab capacity (static shapes: jit caches
    one executable per layout, not per n_live); `snapshot` slices off the
    dead tail on the host."""
    g = cfg.padded_size
    n_cells = g * g
    cap_total = m.base.ids.shape[0] + m.spill.ids.shape[0]

    lb = m.base.live
    base_rank = jnp.cumsum(lb.astype(jnp.int32)) - 1                # (capB,)
    counts_b = jnp.zeros((n_cells + 1,), jnp.int32).at[
        jnp.where(lb, m.base.cell, n_cells)
    ].add(1)[:-1]
    offs_b = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts_b).astype(jnp.int32)]
    )

    in_spill = jnp.arange(m.spill.ids.shape[0]) < m.spill_used
    ls = m.spill.live & in_spill
    counts_s = jnp.zeros((n_cells + 1,), jnp.int32).at[
        jnp.where(ls, m.spill.cell, n_cells)
    ].add(1)[:-1]
    offs_s = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts_s).astype(jnp.int32)]
    )

    # dead slots sort to the end with an out-of-range key; stable argsort
    # preserves arrival order within each cell
    sp_order = jnp.argsort(
        jnp.where(ls, m.spill.cell, n_cells), stable=True
    ).astype(jnp.int32)
    sp_rank = jnp.zeros_like(sp_order).at[sp_order].set(
        jnp.arange(sp_order.shape[0], dtype=jnp.int32)
    )

    pos_b = base_rank + offs_s[jnp.clip(m.base.cell, 0, n_cells - 1)]
    pos_s = offs_b[jnp.clip(m.spill.cell, 0, n_cells - 1) + 1] + sp_rank

    # ONE int scatter builds the inverse permutation; the record fields then
    # move with plain gathers (much cheaper than 6 field scatters on CPU)
    cap_b = m.base.ids.shape[0]
    src = jnp.full((cap_total + 1,), cap_total, jnp.int32)
    src = src.at[jnp.where(lb, pos_b, cap_total)].set(
        jnp.arange(cap_b, dtype=jnp.int32), mode="drop"
    )
    src = src.at[jnp.where(ls, pos_s, cap_total)].set(
        cap_b + jnp.arange(m.spill.ids.shape[0], dtype=jnp.int32), mode="drop"
    )

    def merge(fb, fs, fill):
        pad = jnp.full((1,) + fb.shape[1:], fill, fb.dtype)
        return jnp.concatenate([fb, fs, pad])[src]

    return (
        merge(m.base.points, m.spill.points, 0.0),
        merge(m.base.coords, m.spill.coords, 0.0),
        merge(m.base.labels, m.spill.labels, 0),
        merge(m.base.ids, m.spill.ids, -1),
        offs_b + offs_s,
    )


def snapshot(m: MutableIndex, cfg: GridConfig) -> GridIndex:
    """Freeze the current contents into a standard dense `GridIndex`.

    O(N) order-preserving merge, no argsort over N: live base slots are
    already cell-major (buckets) and keep their relative order; live spill
    records are stable-sorted by cell (arrival order within a cell) and
    interleaved AFTER the bucket records of their cell — exactly the order a
    stable `argsort(cell_id)` over the full point set would produce, which
    is what `build_index` does.  Bit-identical to a rebuild.
    """
    pts, crd, lab, ids, offsets = _merge_snapshot(m, cfg)
    n_out = int(m.n_live)
    index = GridIndex(
        proj=m.proj,
        points_sorted=pts[:n_out],
        coords_sorted=crd[:n_out],
        labels_sorted=lab[:n_out],
        ids_sorted=ids[:n_out],
        offsets=offsets,
        pyramid=m.pyramid,
        sat=None,
        pyr_tiles=m.pyr_tiles,
    )
    if cfg.counter == "sat":
        from repro.core import integral as integral_lib

        index = index._replace(sat=integral_lib.build_sat(m.pyramid[0]))
    return index


def quantized_snapshot(m: MutableIndex, cfg: GridConfig):
    """Freeze the current contents AND their int8 candidate store.

    Returns (GridIndex, quantized.QuantizedStore).  The store is a pure
    function of the snapshot and `snapshot` reproduces `build_index`'s CSR
    order bit-for-bit, so the mutability invariant extends to the quantized
    path with no incremental bookkeeping: requantizing after insert/delete
    yields EXACTLY the store a from-scratch rebuild would (the per-cell
    scales see identical bucket contents in identical order).  This is what
    the `pallas_q8` backend leans on — `build(P1).insert(P2)` serves
    bit-identical quantized results to `build(P1 ∪ P2)`
    (tests/test_quantized.py, tests/test_mutable.py).
    """
    from repro.core import quantized as qz

    index = snapshot(m, cfg)
    return index, qz.quantize_index(index, cfg)


def compact(
    m: MutableIndex,
    cfg: GridConfig,
    slack: float = 0.5,
    min_slack: int = 4,
    spill_capacity: int | None = None,
) -> MutableIndex:
    """Re-layout with fresh per-cell slack: spill merged back into buckets,
    tombstones reclaimed.  Order-preserving (snapshot's O(N) merge), so the
    searchable contents are unchanged; only the slack geometry moves."""
    return from_index(
        snapshot(m, cfg), cfg, slack=slack, min_slack=min_slack,
        spill_capacity=spill_capacity, next_id=int(m.next_id),
    )


def rebuild(m: MutableIndex, cfg: GridConfig, **layout_kw) -> MutableIndex:
    """Full from-scratch rebuild (the heavyweight escape hatch): re-sorts
    the surviving records with `build_index` instead of merging.  Exists as
    the always-correct fallback; `compact()` is the cheap path."""
    snap = snapshot(m, cfg)
    rebuilt = build_index(
        snap.points_sorted, cfg, m.proj,
        labels=snap.labels_sorted, ids=snap.ids_sorted,
    )
    return from_index(rebuilt, cfg, next_id=int(m.next_id), **layout_kw)


# ------------------------------------------------------------- validation ----


def validate_mutable(m: MutableIndex, cfg: GridConfig) -> dict[str, bool]:
    """Structural invariants of the mutable layout itself (slack accounting);
    `grid.validate_invariants(snapshot(m, cfg), cfg)` checks the searchable
    contents."""
    caps = m.cap_offsets[1:] - m.cap_offsets[:-1]
    used_ok = bool(jnp.all((m.used >= 0) & (m.used <= caps)))
    in_spill = jnp.arange(m.spill.ids.shape[0]) < m.spill_used
    live_total = int(jnp.sum(m.base.live)) + int(jnp.sum(m.spill.live & in_spill))
    # every live bucket slot sits inside its cell's handed-out prefix
    slot = jnp.arange(m.base.ids.shape[0], dtype=jnp.int32)
    c = jnp.clip(m.base.cell, 0, caps.shape[0] - 1)
    prefix_ok = bool(jnp.all(
        ~m.base.live
        | ((slot >= m.cap_offsets[c]) & (slot < m.cap_offsets[c] + m.used[c]))
    ))
    no_live_past_spill_used = bool(jnp.all(~m.spill.live | in_spill))
    pyramid_mass = all(int(level.sum()) == int(m.n_live) for level in m.pyramid)
    return {
        "used_within_capacity": used_ok,
        "live_matches_n_live": live_total == int(m.n_live),
        "live_slots_in_used_prefix": prefix_ok,
        "spill_live_in_prefix": no_live_past_spill_used,
        "pyramid_mass_is_n_live": pyramid_mass,
    }


# ------------------------------------------------------------ persistence ----


def state_to_tree(m: MutableIndex) -> dict[str, jax.Array]:
    """Flatten to a plain {name: array} dict (checkpoint-friendly: every
    value is an array, optional fields are encoded by key absence)."""
    out = {
        "proj/matrix": m.proj.matrix, "proj/lo": m.proj.lo, "proj/hi": m.proj.hi,
        "cap_offsets": m.cap_offsets, "used": m.used, "spilled": m.spilled,
        "spill_used": m.spill_used, "next_id": m.next_id, "n_live": m.n_live,
    }
    for slab, tag in ((m.base, "base"), (m.spill, "spill")):
        for field in Slab._fields:
            out[f"{tag}/{field}"] = getattr(slab, field)
    for lv, arr in enumerate(m.pyramid):
        out[f"pyramid/{lv}"] = arr
    if m.pyr_tiles is not None:
        out["pyr_tiles"] = m.pyr_tiles
    return out


def state_from_tree(tree: dict) -> MutableIndex:
    """Inverse of `state_to_tree` (accepts numpy or jax arrays)."""
    a = {k: jnp.asarray(v) for k, v in tree.items()}
    levels = sorted(
        int(k.split("/")[1]) for k in a if k.startswith("pyramid/")
    )
    slab = lambda tag: Slab(**{f: a[f"{tag}/{f}"] for f in Slab._fields})
    return MutableIndex(
        proj=Projection(a["proj/matrix"], a["proj/lo"], a["proj/hi"]),
        base=slab("base"),
        spill=slab("spill"),
        cap_offsets=a["cap_offsets"],
        used=a["used"],
        spilled=a["spilled"].astype(bool),
        spill_used=a["spill_used"],
        pyramid=tuple(a[f"pyramid/{lv}"] for lv in levels),
        pyr_tiles=a.get("pyr_tiles"),
        next_id=a["next_id"],
        n_live=a["n_live"],
    )
