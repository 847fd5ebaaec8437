"""Sharded active-search tier: one index's answer from a store sharded by
grid cell over the devices of a mesh, and MUTABLE while it serves.

Placement is by GRID-CELL OWNERSHIP: cell c lives on shard c % n_shards, so
a point's shard is a pure function of its coordinates (via the shared
projection), never of arrival order.  Each shard holds the CSR records of
its own cells (with GLOBAL point ids and its own `offsets`), and, replicated,
what every shard needs to answer as ONE index over all the points would:

  * the global count pyramid (and its tiles / summed-area table): a cell's
    points all live on its owner, so the per-shard counts partition the
    global ones and their sum IS the global pyramid;
  * the global CSR offsets (`GridIndex.global_offsets`), the prefix sum of
    those counts over cells.

A search (`sharded_search`) runs the `pallas` stages of core/batched.py on
every shard under shard_map: the Eq.-1 loop on the global pyramid (so
radius, count, iterations and convergence are one index's, with no
collective), the window cut to the shard's part of the first `row_cap`
records of each window row in global CSR order (`batched.shard_window_spans`),
and the fused candidate kernel over the shard's records.  The per-shard
top-k lists (k * n_shards values) are then merged with one all_gather and a
(distance, global id) sort, under `search.merge`.  The result equals
`build_index` over the same points searched with the `pallas` backend in
every field, ids up to equal distances, whatever the shard count.

Ownership also makes the sharded tier mutable with the same headline
invariant the dense tier has (core/mutable.py):

    build_sharded(P1).insert(P2).search(Q) == build_sharded(P1 ∪ P2).search(Q)

bit for bit — both sides route every point to the same shard, per-shard
contents land in arrival order (routing preserves batch order), and the
per-shard grids are then bit-identical by the mutable subsystem's own
insert == rebuild invariant.  Each shard owns whole cells, so a `snapshot()`
merge of the per-shard CSR stores reproduces the UNSHARDED `build_index`
order exactly (`merge_to_dense`).

Mutation state is host-driven: `ShardedMutable` holds one
`mutable.MutableIndex` per shard (shapes differ per shard, so they are not
stacked).  Searches run on the stacked, pow2-PADDED snapshot
(`stacked_snapshot`): every per-shard CSR array is padded to a common
power-of-two row capacity so shard_map sees one static shape; rows past
`offsets[-1]` are unreachable (every gather derives its spans from offsets).
A shard whose spill log overflows compacts ALONE (`mutable.insert_tracked`)
— sibling shards are untouched, which keeps the pause local in a serving
tier.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import projection as proj_lib
from repro.core.active_search import SearchResult
from repro.core.grid import (
    GridConfig,
    GridIndex,
    build_index,
    build_pyramid,
    cell_id_of,
)
from repro.core.projection import Projection

SHARD_AXIS = "shards"


def local_mesh() -> Mesh:
    """A 1-D mesh over every local device, on `SHARD_AXIS`: the mesh
    `ActiveSearcher.build` shards over when the plan's backend needs one."""
    return Mesh(np.asarray(jax.local_devices()), (SHARD_AXIS,))


# ------------------------------------------------------------ cell routing ---


def shard_of_cells(cid: jax.Array, n_shards: int) -> jax.Array:
    """Deterministic grid-cell ownership: cell c lives on shard c % n_shards.

    Ownership is a PARTITION of the cells (every cell on exactly one shard),
    and a pure function of the cell — so a point's shard depends only on its
    coordinates and the shared projection, never on arrival order or on what
    else is in the index.  tests/test_sharded_mutable.py holds this to the
    partition property directly.
    """
    return cid % n_shards


def shard_of_points(
    points: jax.Array, cfg: GridConfig, proj: Projection, n_shards: int
) -> jax.Array:
    """(N,) int32 owning shard per point — the routing used by build, insert,
    and the parity oracle in the tests (same `to_grid_coords` + `cell_id_of`
    every other consumer quantizes with)."""
    coords = proj_lib.to_grid_coords(
        proj, jnp.asarray(points, jnp.float32), cfg.grid_size
    )
    return shard_of_cells(cell_id_of(coords, cfg.padded_size), n_shards)


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _pad_records(idx: GridIndex, cap: int) -> GridIndex:
    """Pad the per-shard CSR record arrays to `cap` rows with dead records.

    The pad rows sit PAST offsets[-1], and every consumer (search gathers,
    snapshot slicing, `open_sharded`) derives its spans from offsets — the
    tail is never read, it only makes shard shapes equal for stacking."""
    n = idx.points_sorted.shape[0]
    pad = cap - n
    if pad == 0:
        return idx

    def ext(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)]
        )

    return idx._replace(
        points_sorted=ext(idx.points_sorted, 0.0),
        coords_sorted=ext(idx.coords_sorted, 0.0),
        labels_sorted=ext(idx.labels_sorted, -1),
        ids_sorted=ext(idx.ids_sorted, -1),
    )


@partial(jax.jit, static_argnames=("cap",))
def _stack_piece(idx: GridIndex, cap: int) -> GridIndex:
    """A shard's block of the stacked layout, on the shard's device:
    records padded to `cap`, every leaf given a leading dim of 1 (one
    program per device, where an eager op per leaf would compile and load
    dozens)."""
    return jax.tree.map(lambda a: a[None], _pad_records(idx, cap))


def stack_shard_indexes(
    shards: list[GridIndex], mesh: Mesh, axis: str
) -> GridIndex:
    """Stack per-shard indexes into one GridIndex with a leading shard dim,
    sharded along `axis`.

    Record arrays are padded to a common pow2 capacity first (dead tail, see
    `_pad_records`), so repeated insert/snapshot cycles hit O(log N) distinct
    stacked shapes — the same bounded-compile idiom as mutable.insert's pow2
    batch padding.  Shard s is moved to the mesh's s-th device (a no-op
    where it already lives there) and the stacked array is assembled from
    the per-device pieces, so no device holds them all."""
    cap = _pow2(max(1, max(s.points_sorted.shape[0] for s in shards)))
    pieces = [_stack_piece(jax.device_put(s, d), cap)
              for s, d in zip(shards, mesh.devices.flat)]
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape[1:], sh, list(xs)),
        *pieces,
    )


def _shard_block(a: jax.Array, s: int) -> jax.Array:
    """Block s of a stacked array (leading shard dim), read from the device
    that holds it: an eager `a[s]` of a sharded array is replicated onto
    every device of the mesh."""
    for piece in a.addressable_shards:
        lo = piece.index[0].start or 0
        if lo <= s < lo + piece.data.shape[0]:
            return piece.data[s - lo]
    raise ValueError(f"shard {s} of the stacked array is not addressable")


def _to_state_device(state, *arrays):
    """Put `arrays` on the device that holds a per-shard mutation state."""
    (dev,) = state.base.points.devices()
    return jax.device_put(arrays, dev)


def _device_of(idx: GridIndex):
    (dev,) = idx.offsets.devices()
    return dev


@partial(jax.jit, static_argnames=("n_ranks",))
def _global_counts(counts, n_ranks):
    """The sum of the shards' (pyramid, sat, pyr_tiles); its prefix sum over
    cells (the global CSR offsets); and the cell of each of `n_ranks`
    global CSR positions (padded_size**2 past the last record)."""
    pyramid, sat, tiles = jax.tree.map(
        lambda *xs: functools.reduce(jnp.add, xs), *counts)
    per_cell = pyramid[0].sum(axis=-1).reshape(-1)
    goff = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(per_cell).astype(jnp.int32)])
    n_cells = per_cell.shape[0]
    cells = jnp.repeat(jnp.arange(n_cells, dtype=jnp.int32), per_cell,
                       total_repeat_length=n_ranks)
    cells = jnp.where(jnp.arange(n_ranks) < goff[-1], cells, n_cells)
    return pyramid, sat, tiles, goff, cells


def _with_global_counts(shards: list[GridIndex]) -> list[GridIndex]:
    """Each shard with the counts of ALL shards, on its own device.

    The pyramid, summed-area table and pyramid tiles are linear in the
    per-cell counts, and every cell's points live on one shard, so the
    global ones are the sums of the shards'; `global_offsets` is the prefix
    sum of the global per-cell counts — `build_index`'s offsets over the
    union of the shards' points — and `global_cells` the cell of each
    global CSR position (`batched.shard_window_spans` reads both).  They
    are computed once, on the first shard's device, and copied to the
    others; `global_cells` is pow2-padded, like the records, so inserts
    meet O(log N) shapes."""
    counts = [(s.pyramid, s.sat, s.pyr_tiles) for s in shards]
    n_ranks = _pow2(sum(s.points_sorted.shape[0] for s in shards) + 1)
    whole = _global_counts(jax.device_put(counts, _device_of(shards[0])),
                           n_ranks)
    out = []
    for idx in shards:
        pyramid, sat, tiles, goff, cells = jax.device_put(
            whole, _device_of(idx))
        out.append(idx._replace(pyramid=pyramid, sat=sat, pyr_tiles=tiles,
                                global_offsets=goff, global_cells=cells))
    return out


_build_shard = jax.jit(build_index, static_argnames=("cfg",))


def build_sharded_index(
    points: jax.Array,
    cfg: GridConfig,
    proj: Projection,
    mesh: Mesh,
    axis: str,
    labels: jax.Array | None = None,
    ids: jax.Array | None = None,
) -> GridIndex:
    """Build one grid index per `axis` shard, points routed by cell ownership.

    Returns a GridIndex whose array leaves carry a leading shard dimension of
    size mesh.shape[axis], sharded along `axis`, with the global counts on
    every shard (`_with_global_counts`).  Points are routed on the host and
    each shard is built on its own device, so no device holds every shard's
    build and the builds overlap.  Routing preserves the caller's point
    order within each shard (arrival order is a per-shard notion), and
    `ids` default to the global arange — exactly what an unsharded
    `build_index` would assign.
    """
    n_shards = mesh.shape[axis]
    owner = np.asarray(shard_of_points(points, cfg, proj, n_shards))
    points = np.asarray(points, np.float32)
    n = points.shape[0]
    labels = (np.zeros((n,), np.int32) if labels is None
              else np.asarray(labels, np.int32))
    ids = (np.arange(n, dtype=np.int32) if ids is None
           else np.asarray(ids, np.int32))

    shards = []
    for s, dev in enumerate(mesh.devices.flat):
        sel = np.nonzero(owner == s)[0]  # order-preserving
        p, l, i, pj = jax.device_put(
            (points[sel], labels[sel], ids[sel], proj), dev)
        shards.append(_build_shard(p, cfg, pj, labels=l, ids=i))
    return stack_shard_indexes(_with_global_counts(shards), mesh, axis)


# -------------------------------------------------------------------- search -


def _merge(res: SearchResult, k: int, axis: str) -> SearchResult:
    """The global top-k of the shards' lists: all-gathered, then ordered by
    (distance, global id).  The loop fields are every shard's alike."""
    with jax.named_scope("search.merge"):
        b = res.dists.shape[0]

        def gathered(a):  # (B, k) per shard -> (B, S * k)
            return jnp.moveaxis(lax.all_gather(a, axis), 0, 1).reshape(b, -1)

        # lexicographic (dist, id) sort pins the tie-break to global id
        # order; lax.top_k would break ties by shard position instead
        d, i, l = lax.sort(
            (gathered(res.dists), gathered(res.ids), gathered(res.labels)),
            dimension=1, num_keys=2, is_stable=True,
        )
        top_d = d[:, :k]
        ok = jnp.isfinite(top_d)
        return res._replace(
            ids=jnp.where(ok, i[:, :k], -1),
            dists=top_d,
            labels=jnp.where(ok, l[:, :k], -1),
            valid=ok,
        )


_WINDOW_ROWS = ("offsets", "global_offsets", "global_cells")


@partial(
    jax.jit,
    static_argnames=("cfg", "k", "mode", "mesh", "axis", "interpret",
                     "d_chunk", "adaptive_r0", "op"),
)
def _sharded_call(index, cfg, queries, k, mesh, axis, mode, interpret,
                  d_chunk, adaptive_r0, op):
    from repro.core import batched

    n_shards = mesh.shape[axis]

    def local(idx_stacked, q):
        # the (1, M) blocks of the 1-D arrays the window reads stay blocks:
        # `[0]` of one is a relayout copy of the whole array on a TPU
        rows = {f: getattr(idx_stacked, f) for f in _WINDOW_ROWS}
        idx = jax.tree.map(lambda a: a[0], idx_stacked._replace(
            **dict.fromkeys(_WINDOW_ROWS)))._replace(**rows)
        if op == "classify" and mode == "paper":
            # the count argmax at the final radius: global counts, so every
            # shard holds one index's answer without a candidate
            return batched._paper_vote(idx, cfg, q, k, interpret, adaptive_r0)
        q_grid, res = batched.shard_search(
            idx, cfg, q, k, mode, interpret, d_chunk, adaptive_r0,
            lax.axis_index(axis), n_shards,
        )
        res = _merge(res, k, axis)
        if op == "classify":
            return batched._vote(idx, cfg, q_grid, res, k, interpret)
        return res

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    )
    return fn(index, queries)


def sharded_search(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    axis: str,
    mode: str = "refined",
    interpret: bool | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> SearchResult:
    """Active search over the sharded index: queries (B, d), on any device
    (they are replicated here), -> the SearchResult one index over the same
    points returns.

    Registered as backend "sharded" in the engine registry (core/engine.py).
    Each shard runs the `pallas` stages and kernels (core/batched.py,
    `shard_search`) with the handle's `interpret`, `d_chunk` and
    `adaptive_r0`; see the module docstring for why the answer is one
    index's.

    MERGE TIE-BREAK (pinned, tests/test_mutable.py): the merged list is
    ordered by (distance, global id) — equal distances resolve to ascending
    global id, independent of which shard produced them or where the record
    sits in a shard's CSR store.  Invalid lanes (dist = +inf) sort last.
    """
    return _sharded_call(index, cfg, replicate_queries(queries, mesh), k,
                         mesh, axis, mode, interpret, d_chunk, adaptive_r0,
                         "search")


def sharded_classify(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    axis: str,
    mode: str = "refined",
    interpret: bool | None = None,
    d_chunk: int | None = None,
    adaptive_r0: bool = False,
) -> jax.Array:
    """kNN classification over the sharded index: one index's predictions
    (`batched.classify`), the count fallback and mode="paper" included."""
    return _sharded_call(index, cfg, replicate_queries(queries, mesh), k,
                         mesh, axis, mode, interpret, d_chunk, adaptive_r0,
                         "classify")


def replicate_queries(queries: jax.Array, mesh: Mesh) -> jax.Array:
    return jax.device_put(queries, NamedSharding(mesh, P()))


# ---------------------------------------------------------- sharded mutation -


class ShardedMutable(NamedTuple):
    """Serving-tier mutation state of a sharded handle (host-driven).

    One `mutable.MutableIndex` per shard — per-shard CSR capacities differ,
    so the states live in a host tuple rather than a stacked array tree.
    `next_id` is the GLOBAL auto-id high-water mark (per-shard next_id only
    tracks what that shard has seen).  `compactions`/`compact_s` accumulate
    the shard-LOCAL overflow compactions (`mutable.insert_tracked`): a full
    shard compacts alone while its siblings keep their states untouched —
    the serving tier's pause stays local, and benchmarks/bench_lm_serve.py
    reports it.
    """

    states: tuple
    next_id: int
    compactions: int = 0
    compact_s: float = 0.0

    @property
    def n_shards(self) -> int:
        return len(self.states)

    @property
    def n_live(self) -> int:
        return sum(int(s.n_live) for s in self.states)


def open_sharded(
    index: GridIndex, cfg: GridConfig, spill_capacity: int | None = None
) -> ShardedMutable:
    """Open a STACKED sharded index for mutation.

    Each shard's live prefix (rows before offsets[-1]; the pow2 pad tail is
    dead by construction), with its OWN counts — the global counts on the
    cells it owns, zero elsewhere — becomes its own `mutable.from_index`
    state."""
    from repro.core import mutable as mut

    n_shards = index.offsets.shape[0]
    states = []
    for s in range(n_shards):
        # each shard's state is built on the device that holds the shard
        idx_s = jax.tree.map(lambda a: _shard_block(a, s), index)
        n_s = int(idx_s.offsets[-1])
        base = idx_s.pyramid[0]
        cells = jnp.arange(base.shape[0] * base.shape[1], dtype=jnp.int32)
        owned = (shard_of_cells(cells, n_shards) == s).reshape(base.shape[:2])
        own = jnp.where(owned[..., None], base, 0)
        idx_s = idx_s._replace(
            points_sorted=idx_s.points_sorted[:n_s],
            coords_sorted=idx_s.coords_sorted[:n_s],
            labels_sorted=idx_s.labels_sorted[:n_s],
            ids_sorted=idx_s.ids_sorted[:n_s],
            pyramid=build_pyramid(own, cfg.levels),
            sat=None,
            pyr_tiles=None,
            global_offsets=None,
            global_cells=None,
        )
        states.append(mut.from_index(idx_s, cfg, spill_capacity=spill_capacity))
    next_id = max(int(st.next_id) for st in states) if states else 0
    return ShardedMutable(states=tuple(states), next_id=next_id)


def sharded_insert(
    sm: ShardedMutable,
    cfg: GridConfig,
    points: jax.Array,
    labels: jax.Array | None = None,
    ids: jax.Array | None = None,
) -> ShardedMutable:
    """Route an insert batch to its owning shards and delta-insert per shard.

    Routing is order-preserving, so each shard receives its sub-batch in
    arrival order — together with cell ownership this is what makes sharded
    insert bit-identical to a sharded rebuild of the union.  A shard whose
    spill log overflows compacts ALONE (`mutable.insert_tracked`); siblings
    keep their exact state objects."""
    from repro.core import mutable as mut

    points = jnp.asarray(points, jnp.float32)
    mn = points.shape[0]
    if mn == 0:
        return sm
    if labels is None:
        labels = jnp.zeros((mn,), jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    if ids is None:
        ids = sm.next_id + jnp.arange(mn, dtype=jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)

    proj = sm.states[0].proj
    owner = np.asarray(shard_of_points(points, cfg, proj, sm.n_shards))
    states = list(sm.states)
    compactions, compact_s = sm.compactions, sm.compact_s
    for s in range(len(states)):
        sel = np.nonzero(owner == s)[0]
        if not len(sel):
            continue
        p_s, l_s, i_s = _to_state_device(
            states[s], points[sel], labels[sel], ids[sel]
        )
        states[s], report = mut.insert_tracked(
            states[s], cfg, p_s, labels=l_s, ids=i_s
        )
        compactions += report.compactions
        compact_s += report.compact_s
    return ShardedMutable(
        states=tuple(states),
        next_id=max(sm.next_id, int(ids.max()) + 1),
        compactions=compactions,
        compact_s=compact_s,
    )


def sharded_delete(
    sm: ShardedMutable, cfg: GridConfig, ids: jax.Array, strict: bool = True
) -> ShardedMutable:
    """Tombstone the given global ids on whichever shards carry them.

    Matching is GLOBAL: with strict=True every asked id must be live
    somewhere (same KeyError contract as the dense `mutable.delete`), but a
    given id is allowed to live on several shards (caller-supplied id
    collisions) — every carrier dies, like the dense path."""
    from repro.core import mutable as mut

    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    if ids.shape[0] == 0:
        return sm
    present = [
        np.asarray(mut.ids_live_mask(st, *_to_state_device(st, ids)))
        for st in sm.states
    ]
    if strict:
        matched_any = np.logical_or.reduce(present)
        ids_np = np.asarray(ids)
        n_asked = len(np.unique(ids_np))
        n_matched = len(np.unique(ids_np[matched_any]))
        if n_matched != n_asked:
            raise KeyError(
                f"delete: {n_asked - n_matched} of {n_asked} ids are not "
                f"live in the index (already deleted, or never inserted)"
            )
    states = list(sm.states)
    for s in range(len(states)):
        if present[s].any():
            (ids_s,) = _to_state_device(states[s], ids[present[s]])
            states[s] = mut.delete(states[s], cfg, ids_s, strict=False)
    return sm._replace(states=tuple(states))


def stacked_snapshot(
    sm: ShardedMutable, cfg: GridConfig, mesh: Mesh, axis: str
) -> GridIndex:
    """Freeze the sharded mutation state into the stacked searchable layout
    (per-shard `mutable.snapshot`, the global counts on every shard, then
    pow2-pad + stack along the mesh axis)."""
    from repro.core import mutable as mut

    shards = [mut.snapshot(st, cfg) for st in sm.states]
    return stack_shard_indexes(_with_global_counts(shards), mesh, axis)


def merge_to_dense(index: GridIndex, cfg: GridConfig) -> GridIndex:
    """Merge a stacked sharded index into ONE dense GridIndex, bit-identical
    to `build_index` over the same points in their original arrival order.

    Every grid cell is wholly owned by one shard and routing preserved
    arrival order within each shard, so concatenating the per-shard live
    prefixes in shard order gives a point sequence whose STABLE cell-major
    sort (what `build_index` does) reproduces the unsharded CSR order
    exactly: within a cell all records come from one shard, already in
    arrival order; across cells the sort key decides, same as unsharded."""
    n_shards = index.offsets.shape[0]
    proj = jax.tree.map(lambda a: a[0], index.proj)
    pts, labs, gids = [], [], []
    for s in range(n_shards):
        n_s = int(index.offsets[s, -1])
        pts.append(index.points_sorted[s, :n_s])
        labs.append(index.labels_sorted[s, :n_s])
        gids.append(index.ids_sorted[s, :n_s])
    return build_index(
        jnp.concatenate(pts), cfg, proj,
        labels=jnp.concatenate(labs), ids=jnp.concatenate(gids),
    )


def sharded_stats(sm: ShardedMutable) -> dict:
    """Serving-tier facts for ActiveSearcher.stats() / BENCH_serve.json."""
    return {
        "n_shards": sm.n_shards,
        "shard_points": [int(s.n_live) for s in sm.states],
        "compactions": sm.compactions,
        "compact_s": sm.compact_s,
    }
