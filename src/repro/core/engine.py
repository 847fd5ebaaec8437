"""One searcher handle over every execution path (exported as `repro.api`).

The paper's pipeline (project -> Eq.-1 radius adaptation -> windowed CSR
gather -> re-rank) used to be reachable through four parallel entry points —
`active_search.search/classify`, `core.batched`, `core.exact`,
`core.distributed` — each re-threading the same execution knobs (`backend=`,
`interpret=`, `chunk_size=`) through every signature.  This module collapses
them into a FAISS-style handle:

  plan = ExecutionPlan(backend="pallas", chunk_size=256)
  s = ActiveSearcher.build(points, labels=labels,
                           cfg=GridConfig(n_classes=3), plan=plan)
  res   = s.search(queries, k=11)            # batched SearchResult
  preds = s.classify(queries, k=11)
  cnts  = s.count_at(queries, radii)         # (B, C) circle counts
  s2    = s.with_plan(backend="exact")       # same index, new execution plan
  s3    = s.insert(more_points)              # streaming growth (core/mutable.py)
  live  = s3.delete(stale_ids).snapshot()    # frozen handle, isolated from s3

HOW a search executes lives entirely in the frozen `ExecutionPlan`
(backend name, Pallas interpret override, chunked streaming, donate-able
device placement); WHAT is searched lives in the (index, cfg) pair the
handle carries.  Backends are uniform `BackendImpl` adapters resolved from a
registry (`register_backend`) — `jnp`, `pallas`, `pallas_q8`, `exact` and
`sharded` ship registered; new execution paths plug in without widening any
signature.

Every backend returns the same batched `SearchResult`; the exact brute-force
comparator's `ExactResult` is folded into it with the paper-stat fields
(radius/iters/converged/truncated) defaulted.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import exact as exact_lib
from repro.core import projection as proj_lib
from repro.core import pyramid as pyr
from repro.core.active_search import SearchResult, _search_jnp, run_chunked
from repro.core.grid import (
    GridConfig,
    GridIndex,
    build_index,
    flatten_pyramid_tiles,
)
from repro.kernels.csr_candidate_topk import rows_in_flight

_MODES = ("refined", "paper")


# ------------------------------------------------------------------ plan -----


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """HOW a search executes — frozen, hashable, safe as a jit static arg.

    backend:    registered backend name ("jnp" | "pallas" | "pallas_q8" |
                "exact" | "sharded" | anything added via `register_backend`).
    interpret:  Pallas interpret mode (Pallas-backed backends only).  None
                = `kernels.ops.resolve_interpret`: the interpreter on the
                CPU backend, Mosaic-compiled kernels on a TPU.  True forces
                the interpreter (tests); the facade refuses it on a TPU.
    chunk_size: stream query batches through fixed-size chunks so every
                kernel invocation keeps ONE static shape / VMEM footprint.
                Bit-identical for any value.
    d_chunk:    cap the per-step feature-dim accumulation of the candidate
                re-rank kernels (Pallas candidate-ranking backends only;
                None = reduce each candidate in ONE step, bit-identical to
                the jnp path).  Setting a cap bounds kernel VMEM for very
                large d at the cost of reassociating the float32 distance
                sums.
    rerank_k:   shortlist depth of the quantized candidate stage (backends
                with `supports_quantized` only, i.e. "pallas_q8"): the int8
                coarse pass keeps the best `rerank_k` rows by approximate
                int32 score, then the exact fp32 re-rank ranks ONLY those.
                None = min(max(4k, 32), window*row_cap) at call time.
                Larger values raise recall and cost more re-rank bandwidth;
                must be >= k (validated at the search call, where k is
                known) and is clamped to window*row_cap.
    device:     optional placement target (jax.Device or Sharding); queries
                are `jax.device_put` there before dispatch.
    donate:     donate the caller's query buffer on placement (serve-scale
                batches avoid a copy; requires `device`).
    adaptive_r0: seed each query's Eq.-1 start radius from the pyramid's
                top levels (`pyramid.seed_radius` — a free local-density
                sketch) instead of the global cfg.r0.  Changes only WHERE
                the radius schedule starts, never what the search returns
                at the radius it converges to; backends that run the Eq.-1
                loop (jnp / pallas / pallas_q8 / sharded) support it.
    """

    backend: str = "jnp"
    interpret: bool | None = None
    chunk_size: int | None = None
    d_chunk: int | None = None
    rerank_k: int | None = None
    device: Any = None
    donate: bool = False
    adaptive_r0: bool = False

    def __post_init__(self):
        if self.chunk_size is not None and self.chunk_size <= 0:
            raise ValueError(
                f"chunk_size must be positive, got {self.chunk_size}"
            )
        if self.d_chunk is not None and self.d_chunk <= 0:
            raise ValueError(
                f"d_chunk must be positive, got {self.d_chunk}"
            )
        if self.rerank_k is not None and self.rerank_k <= 0:
            raise ValueError(
                f"rerank_k must be positive, got {self.rerank_k}"
            )
        if self.donate and self.device is None:
            raise ValueError("donate=True needs an ExecutionPlan.device")


# -------------------------------------------------------------- registry -----


@dataclasses.dataclass(frozen=True)
class BackendImpl:
    """Uniform adapter a backend registers.  Each callable takes the
    searcher handle first, so the impl sees (index, cfg, plan) without the
    registry prescribing how they are consumed.

      search(searcher, queries, k, mode)   -> SearchResult   (batched)
      classify(searcher, queries, k, mode) -> (B,) int32
      count_at(searcher, q_grid, radii)    -> (B, C) int32 circle counts

    Any of the three may be None (e.g. `exact` has no `count_at`); the
    facade raises eagerly when an op is missing.
    `supports_interpret` gates `plan.interpret`; `supports_d_chunk` gates
    `plan.d_chunk` (only backends that run a Pallas candidate re-rank can
    honor the accumulation cap); `supports_adaptive_r0` gates
    `plan.adaptive_r0` (only backends that run the Eq.-1 radius loop can
    seed it).  `requires_mesh` marks backends that only work on a sharded
    handle (mesh + axis): `build` shards over every local device for them,
    and eager validators (e.g. lm_serve's CLI check) can reject them up front
    without name-matching.
    `supports_mutation` gates the facade's insert/delete/snapshot mutation
    ops (core/mutable.py deltas on dense handles, distributed.py cell-routed
    deltas on sharded ones): backends that can serve the refreshed snapshot
    declare True; a backend that cannot opts out, and eager validators
    (`lm_serve.py --knn-online`) reject it by capability, not name.
    `supports_quantized` gates `plan.rerank_k`: only backends whose
    candidate stage runs the int8 coarse-shortlist -> exact-re-rank path
    ("pallas_q8") have a shortlist depth to set.
    """

    search: Callable[..., SearchResult] | None = None
    classify: Callable[..., jax.Array] | None = None
    count_at: Callable[..., jax.Array] | None = None
    supports_interpret: bool = False
    supports_d_chunk: bool = False
    supports_adaptive_r0: bool = False
    supports_mutation: bool = False
    supports_quantized: bool = False
    requires_mesh: bool = False
    description: str = ""


_REGISTRY: dict[str, BackendImpl] = {}


def register_backend(name: str, impl: BackendImpl) -> None:
    """Register (or replace) an execution backend under `name`."""
    if not isinstance(impl, BackendImpl):
        raise TypeError(f"impl must be a BackendImpl, got {type(impl).__name__}")
    _REGISTRY[name] = impl


def get_backend(name: str) -> BackendImpl:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered backends: "
            f"{sorted(_REGISTRY)}"
        ) from None


def registered_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ------------------------------------------------------------------ handle ---


@dataclasses.dataclass(frozen=True, eq=False)
class ActiveSearcher:
    """The one handle: (index, cfg) = WHAT is searched, plan = HOW.

    Frozen and cheap to re-plan: `with_plan` returns a new handle sharing
    the same index arrays.  `mesh`/`axis` are only set on a sharded handle
    (`build_sharded`, or `build` with the "sharded" backend), whose searches
    run the `pallas` stages on every shard under shard_map.

    eq=False: the handle wraps jax arrays, so it compares/hashes by
    IDENTITY — pass the hashable `cfg`/`plan` as jit static args, never the
    handle itself.
    """

    index: GridIndex
    cfg: GridConfig
    plan: ExecutionPlan = ExecutionPlan()
    mesh: Any = None
    axis: str | None = None
    # streaming-mutation state (core/mutable.py): None for frozen handles;
    # set by insert/delete so successive mutations reuse the slack layout
    mutable: Any = None

    # -------------------------------------------------------- construction --
    @classmethod
    def build(
        cls,
        points: jax.Array,
        *,
        labels: jax.Array | None = None,
        ids: jax.Array | None = None,
        cfg: GridConfig | None = None,
        plan: ExecutionPlan | None = None,
        proj: proj_lib.Projection | None = None,
    ) -> "ActiveSearcher":
        """Build the paper's grid image + CSR buckets and wrap them in a
        handle.  proj defaults to a PCA projection to the grid plane.

        A plan whose backend needs a mesh (`BackendImpl.requires_mesh`,
        i.e. "sharded") shards the store over every local device
        (`distributed.local_mesh`, through `build_sharded`)."""
        plan = plan or ExecutionPlan()
        if get_backend(plan.backend).requires_mesh:
            from repro.core import distributed as dist

            return cls.build_sharded(
                points, mesh=dist.local_mesh(), axis=dist.SHARD_AXIS,
                labels=labels, ids=ids, cfg=cfg, plan=plan, proj=proj)
        cfg = cfg or GridConfig()
        if proj is None:
            proj = proj_lib.pca_projection(points, grid_dim=2)
        index = build_index(points, cfg, proj, labels=labels, ids=ids)
        return cls(index=index, cfg=cfg, plan=plan)

    @classmethod
    def from_index(
        cls,
        index: GridIndex,
        cfg: GridConfig,
        plan: ExecutionPlan | None = None,
    ) -> "ActiveSearcher":
        """Wrap an already-built GridIndex (e.g. a kNN-LM datastore).

        Pre-layout indexes (pyr_tiles=None, e.g. restored from an old
        checkpoint or assembled by hand) are upgraded HERE, exactly once:
        the pallas count path refuses to re-flatten the pyramid per call.
        """
        if cfg.counter == "pyramid" and index.pyr_tiles is None:
            index = index._replace(
                pyr_tiles=flatten_pyramid_tiles(index.pyramid, cfg.tile)
            )
        return cls(index=index, cfg=cfg, plan=plan or ExecutionPlan())

    @classmethod
    def build_sharded(
        cls,
        points: jax.Array,
        *,
        mesh: Any,
        axis: str,
        labels: jax.Array | None = None,
        ids: jax.Array | None = None,
        cfg: GridConfig | None = None,
        plan: ExecutionPlan | None = None,
        proj: proj_lib.Projection | None = None,
    ) -> "ActiveSearcher":
        """The store sharded by grid cell over `mesh` along `axis`, with
        GLOBAL point ids; a search returns what one index over the same
        points returns (backend "sharded", core/distributed.py).
        `build(plan=ExecutionPlan(backend="sharded"))` calls this with a
        mesh of every local device."""
        from repro.core import distributed as dist

        cfg = cfg or GridConfig()
        if proj is None:
            proj = proj_lib.pca_projection(points, grid_dim=2)
        index = dist.build_sharded_index(
            points, cfg, proj, mesh, axis, labels, ids=ids)
        plan = dataclasses.replace(plan or ExecutionPlan(), backend="sharded")
        return cls(index=index, cfg=cfg, plan=plan, mesh=mesh, axis=axis)

    def with_plan(
        self, plan: ExecutionPlan | None = None, **overrides
    ) -> "ActiveSearcher":
        """Same index, new execution plan (full plan or field overrides).

        Switching `backend=` drops the backend-specific `interpret` and
        `d_chunk` knobs when the new backend does not support them (unless
        explicitly overridden too), so
        `pallas_plan_handle.with_plan(backend="exact")` works instead of
        tripping the capability validation."""
        if plan is not None and overrides:
            raise ValueError("pass a full ExecutionPlan OR field overrides")
        if plan is None and "backend" in overrides:
            impl = _REGISTRY.get(overrides["backend"])
            if impl is not None:
                if not impl.supports_interpret and "interpret" not in overrides:
                    overrides = {**overrides, "interpret": None}
                if not impl.supports_d_chunk and "d_chunk" not in overrides:
                    overrides = {**overrides, "d_chunk": None}
                if (not impl.supports_adaptive_r0
                        and "adaptive_r0" not in overrides):
                    overrides = {**overrides, "adaptive_r0": False}
                if (not impl.supports_quantized
                        and "rerank_k" not in overrides):
                    overrides = {**overrides, "rerank_k": None}
        new = plan if plan is not None else dataclasses.replace(self.plan, **overrides)
        return dataclasses.replace(self, plan=new)

    # ------------------------------------------------------------- mutation --
    def _check_mutation(self) -> None:
        """Eager capability validation: the plan's backend must be able to
        serve the refreshed snapshot a mutation produces."""
        impl = get_backend(self.plan.backend)
        if not impl.supports_mutation:
            mutable_backends = [
                n for n in registered_backends()
                if get_backend(n).supports_mutation
            ]
            raise ValueError(
                f"backend {self.plan.backend!r} does not support mutation "
                f"(BackendImpl.supports_mutation); insert/delete need one "
                f"of {mutable_backends}"
            )

    def _mutable_state(self):
        """Current mutation state, opening the index on first use (per-shard
        MutableIndex states for sharded handles, one state for dense)."""
        from repro.core import mutable as mut

        if self.mutable is not None:
            return self.mutable
        if self.mesh is not None:
            from repro.core import distributed as dist

            return dist.open_sharded(self.index, self.cfg)
        return mut.from_index(self.index, self.cfg)

    def _carry_mutation_stats(self, new, compactions: int, compact_s: float):
        """Accumulate dense-path compaction accounting on the NEW handle
        (same __dict__ side-channel as the exact-order memo; sharded handles
        carry theirs inside ShardedMutable instead)."""
        prev = self.__dict__.get(
            "_mutation_stats", {"compactions": 0, "compact_s": 0.0}
        )
        object.__setattr__(new, "_mutation_stats", {
            "compactions": prev["compactions"] + compactions,
            "compact_s": prev["compact_s"] + compact_s,
        })
        return new

    def insert(
        self,
        points: jax.Array,
        *,
        labels: jax.Array | None = None,
        ids: jax.Array | None = None,
    ) -> "ActiveSearcher":
        """Streaming insert: delta-update the grid, pyramid, and dirty tiles
        (core/mutable.py) and return a NEW handle over the grown index.

        This handle is unchanged (handles are immutable); the returned one
        carries the refreshed dense snapshot plus the slack state, so chained
        inserts keep reusing free bucket slots.  Being a new object, it also
        starts with a cold memoized exact-order cache — the `exact` backend
        re-derives its original-order view over the grown contents instead of
        serving stale memoized arrays.  Results are bit-identical to
        rebuilding from the union of the points (tests/test_mutable.py).

        Sharded handles route every point to its owning shard (grid-cell
        ownership, core/distributed.py) and delta-insert per shard; the same
        insert == rebuild bit-parity holds on the "sharded" backend
        (tests/test_sharded_mutable.py).
        """
        from repro.core import mutable as mut

        self._check_mutation()
        state = self._mutable_state()
        if self.mesh is not None:
            from repro.core import distributed as dist

            state = dist.sharded_insert(state, self.cfg, points,
                                        labels=labels, ids=ids)
            index = dist.stacked_snapshot(state, self.cfg, self.mesh,
                                          self.axis)
            return dataclasses.replace(self, index=index, mutable=state)
        state, report = mut.insert_tracked(state, self.cfg, points,
                                           labels=labels, ids=ids)
        new = dataclasses.replace(
            self, index=mut.snapshot(state, self.cfg), mutable=state
        )
        return self._carry_mutation_stats(
            new, report.compactions, report.compact_s
        )

    def delete(self, ids: jax.Array) -> "ActiveSearcher":
        """Delete by global point id; returns a NEW handle (see `insert`).
        On sharded handles the ids are matched globally (strict accounting
        across shards) and tombstoned on whichever shards carry them."""
        from repro.core import mutable as mut

        self._check_mutation()
        state = self._mutable_state()
        if self.mesh is not None:
            from repro.core import distributed as dist

            state = dist.sharded_delete(state, self.cfg, ids)
            index = dist.stacked_snapshot(state, self.cfg, self.mesh,
                                          self.axis)
            return dataclasses.replace(self, index=index, mutable=state)
        state = mut.delete(state, self.cfg, ids)
        new = dataclasses.replace(
            self, index=mut.snapshot(state, self.cfg), mutable=state
        )
        return self._carry_mutation_stats(new, 0, 0.0)

    def snapshot(self) -> "ActiveSearcher":
        """A frozen handle over the current contents.

        Drops the slack state: later insert/delete on either handle cannot
        affect the other (delta updates build NEW arrays — jax arrays are
        immutable — so a snapshot taken mid-serving stays valid while the
        source keeps mutating).

        On a SHARDED handle this also merges the per-shard stores into ONE
        dense handle (plan switched to the "jnp" backend, mesh dropped)
        whose index is bit-identical to an unsharded `build_index` over the
        same points — cells are wholly shard-owned, so the merge reproduces
        the global CSR order exactly (distributed.merge_to_dense)."""
        if self.mesh is None:
            return dataclasses.replace(self, mutable=None)
        from repro.core import distributed as dist

        dense = dist.merge_to_dense(self.index, self.cfg)
        out = self.with_plan(backend="jnp")
        return dataclasses.replace(
            out, index=dense, mesh=None, axis=None, mutable=None
        )

    # ------------------------------------------------------------- dispatch --
    def check_plan(self) -> BackendImpl:
        """Resolve the plan's backend and validate the plan EAGERLY (before
        any tracing), so every backend raises the same errors for the same
        misuses.  Serving front ends (`launch/serve.DynamicBatcher`) call it
        when they take the handle."""
        impl = get_backend(self.plan.backend)
        if self.plan.interpret is not None and not impl.supports_interpret:
            raise ValueError(
                f"interpret= only applies to Pallas-backed backends; "
                f"backend {self.plan.backend!r} does not support it"
            )
        if self.plan.interpret and jax.default_backend() == "tpu":
            raise ValueError(
                "interpret=True would run the Pallas interpreter on a TPU; "
                "the served path runs the Mosaic-compiled kernels only "
                "(leave interpret=None)"
            )
        if self.plan.d_chunk is not None and not impl.supports_d_chunk:
            raise ValueError(
                f"d_chunk= only applies to Pallas candidate-ranking "
                f"backends; backend {self.plan.backend!r} does not "
                f"support it"
            )
        if self.plan.adaptive_r0 and not impl.supports_adaptive_r0:
            raise ValueError(
                f"adaptive_r0= only applies to backends that run the Eq.-1 "
                f"radius loop; backend {self.plan.backend!r} does not "
                f"support it"
            )
        if self.plan.rerank_k is not None and not impl.supports_quantized:
            raise ValueError(
                f"rerank_k= only applies to quantized-candidate backends "
                f"(BackendImpl.supports_quantized); backend "
                f"{self.plan.backend!r} does not support it"
            )
        return impl

    def _impl(self, op: str) -> Callable:
        """The plan's `op` callable, after `check_plan`."""
        fn = getattr(self.check_plan(), op)
        if fn is None:
            raise ValueError(
                f"backend {self.plan.backend!r} does not implement {op}()"
            )
        return fn

    def _place(self, arr: jax.Array) -> jax.Array:
        if self.plan.device is None:
            return arr
        return jax.device_put(arr, self.plan.device, donate=self.plan.donate)

    @staticmethod
    def _check_mode(mode: str) -> None:
        if mode not in _MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of {_MODES}")

    # ------------------------------------------------------------------ ops --
    def search(self, queries: jax.Array, k: int, mode: str = "refined") -> SearchResult:
        """Batched active search: queries (B, d) -> SearchResult, leading B.

        mode="paper":   members of the final Eq.-1 circle, ranked by
                        grid-pixel distance.
        mode="refined": candidates re-ranked by the true metric in the
                        original space (recommended).
        """
        self._check_mode(mode)
        fn = self._impl("search")
        q = self._place(jnp.asarray(queries))
        with TraceAnnotation("search.call"):
            return run_chunked(lambda c: fn(self, c, k, mode), q,
                               self.plan.chunk_size)

    def classify(self, queries: jax.Array, k: int, mode: str = "refined") -> jax.Array:
        """kNN classification: (B, d) -> (B,) int32 class predictions."""
        self._check_mode(mode)
        if self.cfg.n_classes <= 0:
            raise ValueError("classify() needs an index built with n_classes > 0")
        fn = self._impl("classify")
        q = self._place(jnp.asarray(queries))
        with TraceAnnotation("search.call"):
            return run_chunked(lambda c: fn(self, c, k, mode), q,
                               self.plan.chunk_size)

    def count_at(self, queries: jax.Array, radii: jax.Array) -> jax.Array:
        """Per-class circle counts (B, C) at the given radii (pixels) — the
        paper's count primitive, exposed for diagnostics.
        queries are ORIGINAL-space (B, d); projection happens here.
        plan.chunk_size streams (q_grid, radius) pairs like search does."""
        fn = self._impl("count_at")
        q = self._place(jnp.asarray(queries))
        q_grid = proj_lib.to_grid_coords(self.index.proj, q, self.cfg.grid_size)
        return run_chunked(
            lambda qr: fn(self, qr[0], qr[1]),
            (q_grid, jnp.asarray(radii, jnp.int32)),
            self.plan.chunk_size,
        )

    def stats(self) -> dict[str, Any]:
        """Static facts about the handle: index shape/memory + plan."""
        idx, cfg = self.index, self.cfg
        tile_bytes = (
            0 if idx.pyr_tiles is None
            else idx.pyr_tiles.size * idx.pyr_tiles.dtype.itemsize
        )
        pyramid_bytes = sum(a.size * a.dtype.itemsize for a in idx.pyramid)
        csr_bytes = sum(
            a.size * a.dtype.itemsize
            for a in (idx.points_sorted, idx.coords_sorted,
                      idx.labels_sorted, idx.ids_sorted, idx.offsets)
        )
        if self.mesh is not None and self.mutable is None:
            # live records per shard (pad rows excluded): the balance of
            # cell ownership
            live = [int(n) for n in jax.device_get(idx.offsets[:, -1])]
            mutation_stats = {"n_shards": len(live), "shard_points": live}
        elif self.mutable is None:
            mutation_stats = {}
        elif self.mesh is not None:
            from repro.core import distributed as dist

            mutation_stats = dist.sharded_stats(self.mutable)
        else:
            mutation_stats = {
                "free_bucket_slots": int(self.mutable.free_bucket_slots),
                "spill_used": int(self.mutable.spill_used),
                "spill_capacity": self.mutable.spill_capacity,
                **self.__dict__.get(
                    "_mutation_stats", {"compactions": 0, "compact_s": 0.0}
                ),
            }
        return {
            # LIVE record count from the CSR offsets: dense handles end at
            # offsets[-1] == N, sharded handles sum per-shard live prefixes
            # — the stacked layout's pow2 pad rows must NOT count
            "n_points": int(jnp.sum(idx.offsets[..., -1])),
            "dim": int(idx.points_sorted.shape[-1]),
            "grid_size": cfg.grid_size,
            "padded_size": cfg.padded_size,
            "levels": cfg.levels,
            "n_classes": cfg.n_classes,
            "metric": cfg.metric,
            "counter": cfg.counter,
            "backend": self.plan.backend,
            "plan": self.plan,
            "sharded": self.mesh is not None,
            "pyramid_bytes": int(pyramid_bytes),
            "pyr_tiles_bytes": int(tile_bytes),
            "csr_bytes": int(csr_bytes),
            "mutable": self.mutable is not None,
            # window rows the candidate kernel keeps in flight per slab
            "candidate_rows_in_flight": rows_in_flight(
                cfg.window, cfg.row_cap, int(idx.points_sorted.shape[-1])
            ),
            **mutation_stats,
        }


# ------------------------------------------------------ built-in backends ----


def _jnp_search(s: ActiveSearcher, queries, k, mode):
    return _search_jnp(s.index, s.cfg, queries, k, mode,
                       adaptive_r0=s.plan.adaptive_r0)


def _jnp_classify(s: ActiveSearcher, queries, k, mode):
    from repro.core.active_search import _classify_jnp

    return _classify_jnp(s.index, s.cfg, queries, k, mode,
                         adaptive_r0=s.plan.adaptive_r0)


def _jnp_count_at(s: ActiveSearcher, q_grid, radii):
    return _count_jnp(s.index, s.cfg, q_grid, radii)


@partial(jax.jit, static_argnames=("cfg",))
def _count_jnp(index: GridIndex, cfg: GridConfig, q_grid, radii):
    return jax.vmap(lambda g, r: pyr.count_in_circle(index, cfg, g, r))(
        q_grid, radii
    )


def _pallas_search(s: ActiveSearcher, queries, k, mode):
    from repro.core import batched

    return batched.search(
        s.index, s.cfg, queries, k, mode=mode, interpret=s.plan.interpret,
        d_chunk=s.plan.d_chunk, adaptive_r0=s.plan.adaptive_r0,
    )


def _pallas_classify(s: ActiveSearcher, queries, k, mode):
    from repro.core import batched

    return batched.classify(
        s.index, s.cfg, queries, k, mode=mode, interpret=s.plan.interpret,
        d_chunk=s.plan.d_chunk, adaptive_r0=s.plan.adaptive_r0,
    )


def _quantized_store(s: ActiveSearcher):
    """The handle's int8 candidate store (core/quantized.py), memoized.

    Same __dict__ side-channel as `_exact_ordered`: frozen dataclasses
    still allow attribute caching, the quantization runs once per handle,
    and every mutation (insert/delete/snapshot) returns a NEW handle, so
    the memo can never serve a store for stale contents.  Never cached
    under a trace (tracers on the handle would leak into later calls)."""
    from repro.core import quantized as qz

    cached = s.__dict__.get("_quantized_store_cache")
    if cached is not None:
        return cached
    store = qz.quantize_index(s.index, s.cfg)
    if not any(isinstance(a, jax.core.Tracer) for a in store):
        object.__setattr__(s, "_quantized_store_cache", store)
    return store


def _pallas_q8_search(s: ActiveSearcher, queries, k, mode):
    from repro.core import batched

    return batched.search_q8(
        s.index, _quantized_store(s), s.cfg, queries, k, mode=mode,
        rerank_k=s.plan.rerank_k, interpret=s.plan.interpret,
        d_chunk=s.plan.d_chunk, adaptive_r0=s.plan.adaptive_r0,
    )


def _pallas_q8_classify(s: ActiveSearcher, queries, k, mode):
    from repro.core import batched

    return batched.classify_q8(
        s.index, _quantized_store(s), s.cfg, queries, k, mode=mode,
        rerank_k=s.plan.rerank_k, interpret=s.plan.interpret,
        d_chunk=s.plan.d_chunk, adaptive_r0=s.plan.adaptive_r0,
    )


def _pallas_count_at(s: ActiveSearcher, q_grid, radii):
    from repro.core import batched

    return batched.batched_counts(s.index, s.cfg, q_grid, radii, s.plan.interpret)


def _exact_ordered(s: ActiveSearcher):
    """CSR arrays restored to original-id order, so the exact comparator sees
    the datastore exactly as the caller supplied it (bit-identical tie
    breaks vs pre-facade `exact.knn(points, ...)` calls).

    Memoized on the handle (frozen dataclasses still allow __dict__
    caching): the O(N log N) argsort + O(N d) gathers run once per handle,
    not once per call/chunk.  NEVER cached under a trace — inside
    jit/eval_shape the reorder produces tracers, and storing those on the
    handle would leak them into later calls (UnexpectedTracerError)."""
    cached = s.__dict__.get("_exact_ordered_cache")
    if cached is not None:
        return cached
    index = s.index
    order = jnp.argsort(index.ids_sorted)
    out = (
        index.points_sorted[order],
        index.labels_sorted[order],
        index.ids_sorted[order],
    )
    if not any(isinstance(a, jax.core.Tracer) for a in out):
        object.__setattr__(s, "_exact_ordered_cache", out)
    return out


def _exact_search(s: ActiveSearcher, queries, k, mode):
    """Brute-force comparator folded into the uniform SearchResult: the
    paper-stat fields (radius/count/iters/converged/truncated) are defaulted
    since exact kNN has no Eq.-1 loop.  `mode` is accepted for interface
    uniformity; exact distances are always original-space."""
    pts, labels, ids = _exact_ordered(s)
    res = exact_lib.knn(
        jnp.asarray(queries, jnp.float32), pts, k, metric=s.cfg.metric
    )
    b = res.ids.shape[0]
    valid = jnp.isfinite(res.dists) & (res.ids >= 0)
    pos = jnp.clip(res.ids, 0, pts.shape[0] - 1)
    return SearchResult(
        ids=jnp.where(valid, ids[pos], -1),
        dists=jnp.where(valid, res.dists, jnp.inf).astype(jnp.float32),
        labels=jnp.where(valid, labels[pos], -1),
        valid=valid,
        radius=jnp.zeros((b,), jnp.int32),
        count=jnp.sum(valid, axis=1).astype(jnp.int32),
        iters=jnp.zeros((b,), jnp.int32),
        converged=jnp.ones((b,), bool),
        truncated=jnp.zeros((b,), bool),
    )


def _exact_classify(s: ActiveSearcher, queries, k, mode):
    pts, labels, _ = _exact_ordered(s)
    return exact_lib.classify(
        jnp.asarray(queries, jnp.float32), pts, labels, k,
        s.cfg.n_classes, metric=s.cfg.metric,
    )


def _sharded(s: ActiveSearcher, fn, queries, k, mode):
    if s.mesh is None or s.axis is None:
        raise ValueError(
            "backend 'sharded' needs a sharded handle: ActiveSearcher.build"
            " with this plan, or build_sharded (mesh + axis)"
        )
    return fn(
        s.index, s.cfg, queries, k, s.mesh, s.axis, mode=mode,
        interpret=s.plan.interpret, d_chunk=s.plan.d_chunk,
        adaptive_r0=s.plan.adaptive_r0,
    )


def _sharded_search(s: ActiveSearcher, queries, k, mode):
    from repro.core import distributed as dist

    return _sharded(s, dist.sharded_search, queries, k, mode)


def _sharded_classify(s: ActiveSearcher, queries, k, mode):
    from repro.core import distributed as dist

    return _sharded(s, dist.sharded_classify, queries, k, mode)


register_backend("jnp", BackendImpl(
    search=_jnp_search, classify=_jnp_classify, count_at=_jnp_count_at,
    supports_adaptive_r0=True, supports_mutation=True,
    description="per-query reference pipeline under jax.vmap (pure lax/jnp)",
))
register_backend("pallas", BackendImpl(
    search=_pallas_search, classify=_pallas_classify,
    count_at=_pallas_count_at, supports_interpret=True,
    supports_d_chunk=True, supports_adaptive_r0=True,
    supports_mutation=True,
    description="batched kernel pipeline: level-scheduled "
                "tile_count_multilevel + FUSED csr_candidate_topk (candidate "
                "rows DMA'd straight from the CSR store; no (B, w*row_cap) "
                "intermediate) (core/batched.py)",
))
register_backend("pallas_q8", BackendImpl(
    search=_pallas_q8_search, classify=_pallas_q8_classify,
    count_at=_pallas_count_at, supports_interpret=True,
    supports_d_chunk=True, supports_adaptive_r0=True,
    supports_mutation=True, supports_quantized=True,
    description="quantized candidate stage: int8 store DMA + int32 VPU "
                "scoring shortlists top-rerank_k rows, then an exact fp32 "
                "re-rank of the shortlist emits the final (dists, ids).  "
                "Recall contract vs the exact backends (approximate in "
                "WHICH rows shortlist, never in returned distances); "
                "counting stage identical to 'pallas' "
                "(core/quantized.py + core/batched.py)",
))
register_backend("exact", BackendImpl(
    search=_exact_search, classify=_exact_classify, supports_mutation=True,
    description="blocked brute-force kNN — the paper's 'original kNN' "
                "comparator (core/exact.py)",
))
register_backend("sharded", BackendImpl(
    search=_sharded_search, classify=_sharded_classify, requires_mesh=True,
    supports_interpret=True, supports_d_chunk=True,
    supports_adaptive_r0=True, supports_mutation=True,
    description="store sharded by grid cell over a device mesh: the pallas "
                "stages per shard under shard_map on the global pyramid, "
                "each shard's part of one index's window, and a (dist, "
                "global id) top-k merge — one index's answer; mutation "
                "routed by cell ownership (core/distributed.py)",
))


__all__ = [
    "ActiveSearcher",
    "BackendImpl",
    "ExecutionPlan",
    "SearchResult",
    "get_backend",
    "register_backend",
    "registered_backends",
]
