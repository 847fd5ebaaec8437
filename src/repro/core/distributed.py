"""Sharded active-search tier: query cost independent of N *per shard*,
with the index staying MUTABLE while it serves.

Cluster-scale layout (DESIGN.md §2): the datastore of N points is sharded
along a mesh axis; every shard builds its OWN grid over the SAME global
extents, with GLOBAL point ids.  A query (replicated) runs active search on
all shards in parallel under shard_map, then the per-shard top-k lists
(k * n_shards values — small) are merged with one all_gather + a
(distance, global id) lexicographic sort.

Per-shard query cost stays N-independent (the paper's property); the merge is
O(k * n_shards), independent of N.

Placement is by GRID-CELL OWNERSHIP: cell c lives on shard c % n_shards, so
a point's shard is a pure function of its coordinates (via the shared
projection), never of arrival order.  That determinism is what makes the
sharded tier mutable with the same headline invariant the dense tier has
(core/mutable.py):

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

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import projection as proj_lib
from repro.core.active_search import SearchResult
from repro.core.grid import GridConfig, GridIndex, build_index, cell_id_of
from repro.core.projection import Projection


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
    padded = [_pad_records(s, cap) for s in shards]
    sh = NamedSharding(mesh, P(axis))
    devs = list(mesh.devices.flat)
    return jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape, sh,
            [jax.device_put(x[None], d) for x, d in zip(xs, devs)],
        ),
        *padded,
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
    size mesh.shape[axis], sharded along `axis`.  Routing preserves the
    caller's point order within each shard (arrival order is a per-shard
    notion), and `ids` default to the global arange — exactly what an
    unsharded `build_index` would assign.
    """
    n_shards = mesh.shape[axis]
    points = jnp.asarray(points, jnp.float32)
    n = points.shape[0]
    if labels is None:
        labels = jnp.zeros((n,), dtype=jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    if ids is None:
        ids = jnp.arange(n, dtype=jnp.int32)
    ids = jnp.asarray(ids, jnp.int32)

    owner = np.asarray(shard_of_points(points, cfg, proj, n_shards))
    shards = []
    for s in range(n_shards):
        sel = np.nonzero(owner == s)[0]  # order-preserving
        shards.append(
            build_index(points[sel], cfg, proj, labels=labels[sel],
                        ids=ids[sel])
        )
    return stack_shard_indexes(shards, mesh, axis)


# -------------------------------------------------------------------- search -


@partial(
    jax.jit,
    static_argnames=("cfg", "k", "mode", "axis", "mesh", "adaptive_r0"),
)
def sharded_search(
    index: GridIndex,
    cfg: GridConfig,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    axis: str,
    mode: str = "refined",
    adaptive_r0: bool = False,
) -> SearchResult:
    """Active search over the sharded index; queries (B, d) replicated.

    Registered as backend "sharded" in the engine registry (core/engine.py):
    every shard runs its OWN per-shard ActiveSearcher handle (jnp plan) under
    shard_map, then the per-shard top-k lists are merged.  Returns the
    globally merged top-k per query (ids are global point ids).
    `adaptive_r0` seeds each shard's Eq.-1 loop from that shard's OWN
    pyramid (density differs per shard, so seeds do too — exactly like every
    other per-shard Eq.-1 quantity).

    MERGE TIE-BREAK (pinned, tests/test_mutable.py): the merged list is
    ordered by (distance, global id) — equal distances resolve to ascending
    global id, independent of which shard produced them or where the record
    sits in a shard's CSR store.  Invalid lanes (dist = +inf) sort last.
    """
    # function-level import: engine registers this module's search as a
    # backend, so a top-level import would be circular
    from repro.core import engine as eng

    local_plan = eng.ExecutionPlan(backend="jnp", adaptive_r0=adaptive_r0)

    def local_query(idx_stacked, q):
        idx = jax.tree.map(lambda a: a[0], idx_stacked)
        shard = eng.ActiveSearcher(index=idx, cfg=cfg, plan=local_plan)
        res = shard.search(q, k, mode=mode)                  # (B, k) per-shard
        d_all = lax.all_gather(res.dists, axis)               # (S, B, k)
        i_all = lax.all_gather(res.ids, axis)
        l_all = lax.all_gather(res.labels, axis)
        b = q.shape[0]
        d_flat = jnp.moveaxis(d_all, 0, 1).reshape(b, -1)     # (B, S*k)
        i_flat = jnp.moveaxis(i_all, 0, 1).reshape(b, -1)
        l_flat = jnp.moveaxis(l_all, 0, 1).reshape(b, -1)
        # lexicographic (dist, id) sort pins the tie-break to global id
        # order; lax.top_k would break ties by shard position instead
        d_sorted, i_sorted, l_sorted = lax.sort(
            (d_flat, i_flat, l_flat), dimension=1, num_keys=2,
            is_stable=True,
        )
        top_d = d_sorted[:, :k]
        ok = jnp.isfinite(top_d)
        merged = SearchResult(
            ids=jnp.where(ok, i_sorted[:, :k], -1),
            dists=top_d,
            labels=jnp.where(ok, l_sorted[:, :k], -1),
            valid=ok,
            # diagnostics: reduce across shards
            radius=lax.pmax(res.radius, axis),
            count=lax.psum(res.count, axis),
            iters=lax.pmax(res.iters, axis),
            converged=jnp.logical_and(
                lax.pmin(res.converged.astype(jnp.int32), axis) > 0, True
            ),
            truncated=lax.pmax(res.truncated.astype(jnp.int32), axis) > 0,
        )
        return merged

    in_specs = (P(axis), P())
    out_specs = P()
    fn = jax.shard_map(
        local_query, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(index, queries)


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
    dead by construction) becomes its own `mutable.from_index` state."""
    from repro.core import mutable as mut

    n_shards = index.offsets.shape[0]
    states = []
    for s in range(n_shards):
        # each shard's state is built on the device that holds the shard
        idx_s = jax.tree.map(lambda a: _shard_block(a, s), index)
        n_s = int(idx_s.offsets[-1])
        idx_s = idx_s._replace(
            points_sorted=idx_s.points_sorted[:n_s],
            coords_sorted=idx_s.coords_sorted[:n_s],
            labels_sorted=idx_s.labels_sorted[:n_s],
            ids_sorted=idx_s.ids_sorted[:n_s],
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
    (per-shard `mutable.snapshot`, then pow2-pad + stack along the mesh
    axis)."""
    from repro.core import mutable as mut

    shards = [mut.snapshot(st, cfg) for st in sm.states]
    return stack_shard_indexes(shards, mesh, axis)


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
