"""The mutable index subsystem (core/mutable.py + the facade's
insert/delete/snapshot): the headline invariant is that INSERT-THEN-SEARCH
is bit-identical to REBUILD-THEN-SEARCH for every registered backend, with
delete, overflow escape hatches, snapshot isolation, checkpoint round-trips,
and the online retrieval_memory / kNN-LM growth paths riding along."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.checkpoint.store import CheckpointManager
from repro.core import knn_lm
from repro.core import mutable as mut
from repro.core import retrieval_memory as rmem
from repro.core.grid import GridConfig, build_index, validate_invariants
from repro.core.projection import identity_projection

CFG = GridConfig(grid_size=128, tile=16, n_classes=3, window=48, row_cap=48,
                 r0=8, k_slack=2.0)


def _data(rng, n, scale=1.0, d=2):
    pts = jnp.asarray(rng.normal(size=(n, d)) * scale, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)
    return pts, labels


def _assert_index_equal(a, b):
    for f in ("points_sorted", "coords_sorted", "labels_sorted",
              "ids_sorted", "offsets"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f
        )
    assert len(a.pyramid) == len(b.pyramid)
    for lv, (pa, pb) in enumerate(zip(a.pyramid, b.pyramid)):
        np.testing.assert_array_equal(
            np.asarray(pa), np.asarray(pb), err_msg=f"pyramid[{lv}]"
        )
    assert (a.pyr_tiles is None) == (b.pyr_tiles is None)
    if a.pyr_tiles is not None:
        np.testing.assert_array_equal(
            np.asarray(a.pyr_tiles), np.asarray(b.pyr_tiles), err_msg="pyr_tiles"
        )


def _assert_results_equal(a, b, msg=""):
    for field in api.SearchResult._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=f"{msg}:{field}",
        )


# ------------------------------------------------------------ core parity ----


def test_insert_snapshot_bit_identical_to_rebuild(rng):
    """snapshot(insert(from_index(build(P1)), P2)) == build(P1 u P2) on every
    array of the index — CSR order, offsets, pyramid, flattened tiles."""
    pts, labels = _data(rng, 2500)
    proj = identity_projection(pts)
    n1 = 2000
    full = build_index(pts, CFG, proj, labels=labels)
    state = mut.from_index(build_index(pts[:n1], CFG, proj, labels=labels[:n1]), CFG)
    state = mut.insert(state, CFG, pts[n1:], labels=labels[n1:])
    _assert_index_equal(full, mut.snapshot(state, CFG))
    assert all(mut.validate_mutable(state, CFG).values())


def test_delete_bit_identical_to_rebuild_of_survivors(rng):
    pts, labels = _data(rng, 1500)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts, CFG, proj, labels=labels), CFG)
    del_ids = jnp.asarray(rng.choice(1500, size=400, replace=False), jnp.int32)
    state = mut.delete(state, CFG, del_ids)
    keep = np.setdiff1d(np.arange(1500), np.asarray(del_ids))
    ref = build_index(pts[keep], CFG, proj, labels=labels[keep],
                      ids=jnp.asarray(keep, jnp.int32))
    _assert_index_equal(ref, mut.snapshot(state, CFG))


def test_interleaved_insert_delete_parity(rng):
    """Multiple rounds of mixed mutation stay bit-identical to a one-shot
    build of the surviving points, starting from an EMPTY index."""
    pts, labels = _data(rng, 900)
    proj = identity_projection(pts)
    empty = build_index(jnp.zeros((0, 2), jnp.float32), CFG, proj,
                        labels=jnp.zeros((0,), jnp.int32))
    state = mut.from_index(empty, CFG)
    state = mut.insert(state, CFG, pts[:300], labels=labels[:300])
    state = mut.insert(state, CFG, pts[300:700], labels=labels[300:700])
    state = mut.delete(state, CFG, jnp.arange(100, 250, dtype=jnp.int32))
    state = mut.insert(state, CFG, pts[700:], labels=labels[700:])
    keep = np.r_[0:100, 250:900]
    ref = build_index(pts[keep], CFG, proj, labels=labels[keep],
                      ids=jnp.asarray(keep, jnp.int32))
    _assert_index_equal(ref, mut.snapshot(state, CFG))
    inv = validate_invariants(mut.snapshot(state, CFG), CFG)
    assert all(inv.values()), inv


def test_facade_insert_search_parity_all_backends(rng):
    """The acceptance invariant: build(P1).insert(P2).search(Q) equals
    build(P1 u P2).search(Q) — ids, distances, AND the Eq.-1 stat fields —
    for EVERY registered backend that can search.  Mesh-requiring backends
    (sharded) run the same matrix on build_sharded handles over however
    many devices the process sees (8 under the CI multi-device job)."""
    from repro.core import distributed as D

    pts, labels = _data(rng, 1200)
    proj = identity_projection(pts)
    n1 = 900
    s1 = api.ActiveSearcher.from_index(
        build_index(pts[:n1], CFG, proj, labels=labels[:n1]), CFG
    )
    grown = s1.insert(pts[n1:], labels=labels[n1:])
    ref = api.ActiveSearcher.from_index(
        build_index(pts, CFG, proj, labels=labels), CFG
    )
    q = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
    sh_grown = api.ActiveSearcher.build_sharded(
        pts[:n1], mesh=mesh, axis="data", labels=labels[:n1], cfg=CFG,
        proj=proj,
    ).insert(pts[n1:], labels=labels[n1:])
    sh_ref = api.ActiveSearcher.build_sharded(
        pts, mesh=mesh, axis="data", labels=labels, cfg=CFG, proj=proj)
    # the sweep must include the quantized backend: its store is DERIVED
    # from the snapshot, so insert == rebuild has to survive requantization
    assert "pallas_q8" in api.registered_backends()
    for name in api.registered_backends():
        impl = api.get_backend(name)
        if impl.search is None:
            continue
        if impl.requires_mesh:
            a_h, b_h = sh_grown.with_plan(backend=name), \
                sh_ref.with_plan(backend=name)
            qq = D.replicate_queries(q, mesh)
        else:
            a_h, b_h, qq = grown.with_plan(backend=name), \
                ref.with_plan(backend=name), q
        _assert_results_equal(a_h.search(qq, 8), b_h.search(qq, 8), msg=name)
        np.testing.assert_array_equal(
            np.asarray(a_h.classify(qq, 8)),
            np.asarray(b_h.classify(qq, 8)),
            err_msg=name,
        )
        if impl.supports_adaptive_r0:
            # adaptive seeding reads the pyramid's TOP levels, which delta
            # updates must keep consistent — grown vs rebuilt must agree on
            # the full adaptive schedule too
            a = a_h.with_plan(backend=name, adaptive_r0=True).search(qq, 8)
            b = b_h.with_plan(backend=name, adaptive_r0=True).search(qq, 8)
            _assert_results_equal(a, b, msg=f"{name}:adaptive_r0")


def test_facade_delete_then_exact_backend_forgets_points(rng):
    """Deleted points are gone from every backend, including the exact
    comparator whose memoized original-order cache must NOT survive the
    mutation (the returned handle is a new object with a cold cache)."""
    pts, labels = _data(rng, 600)
    proj = identity_projection(pts)
    s = api.ActiveSearcher.from_index(
        build_index(pts, CFG, proj, labels=labels), CFG,
        plan=api.ExecutionPlan(backend="exact"),
    )
    q = pts[:4]
    before = s.search(q, 1)  # also warms the exact-order memo on s
    assert "_exact_ordered_cache" in s.__dict__
    np.testing.assert_array_equal(np.asarray(before.ids[:, 0]),
                                  np.arange(4))
    s2 = s.delete(jnp.arange(4, dtype=jnp.int32))
    assert "_exact_ordered_cache" not in s2.__dict__
    after = s2.search(q, 1)
    assert not np.intersect1d(np.asarray(after.ids), np.arange(4)).size
    # the source handle still sees the original contents
    _assert_results_equal(before, s.search(q, 1))


# ------------------------------------------------------- slack management ----


def test_spill_overflow_raises_or_compacts(rng):
    pts, _ = _data(rng, 500)
    far = jnp.asarray(rng.normal(size=(64, 2)) * 3, jnp.float32)  # fresh cells
    proj = identity_projection(jnp.concatenate([pts, far]))
    index = build_index(pts, CFG, proj)
    state = mut.from_index(index, CFG, spill_capacity=4)
    with pytest.raises(mut.BucketOverflow, match="spill slots"):
        mut.insert(state, CFG, far, on_overflow="raise")
    grown = mut.insert(state, CFG, far)  # default: compact + retry
    ref = build_index(jnp.concatenate([pts, far]), CFG, proj)
    _assert_index_equal(ref, mut.snapshot(grown, CFG))


def test_overflow_compact_retry_survives_slack_retightening(rng):
    """compact() shrinks bucket slack, so points that FIT the old layout can
    spill in the fresh one — the retry's spill capacity must cover the whole
    batch, not just the pre-compact spill count (regression)."""
    # one crowded cell: lots of tombstone slack that compact reclaims
    pts = jnp.zeros((100, 2), jnp.float32) + 0.5
    far = jnp.asarray(rng.normal(size=(8, 2)) * 3 + 10, jnp.float32)
    proj = identity_projection(jnp.concatenate([pts, far]))
    state = mut.from_index(build_index(pts, CFG, proj), CFG, spill_capacity=4)
    state = mut.delete(state, CFG, jnp.arange(90, dtype=jnp.int32))
    # 40 points into the crowded cell (fit pre-compact slack) + 8 into fresh
    # cells (must spill; 8 > spill_capacity=4 forces the compact retry)
    batch = jnp.concatenate([jnp.zeros((40, 2), jnp.float32) + 0.5, far])
    grown = mut.insert(state, CFG, batch)  # must not raise
    assert int(grown.n_live) == 10 + 48
    keep_ids = np.r_[90:100, 100:148]
    snap = mut.snapshot(grown, CFG)
    assert set(np.asarray(snap.ids_sorted).tolist()) == set(keep_ids.tolist())
    assert all(validate_invariants(snap, CFG).values())


def test_compact_preserves_contents_and_frees_slack(rng):
    pts, labels = _data(rng, 800)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts, CFG, proj, labels=labels), CFG)
    state = mut.delete(state, CFG, jnp.arange(0, 200, dtype=jnp.int32))
    packed = mut.compact(state, CFG)
    _assert_index_equal(mut.snapshot(state, CFG), mut.snapshot(packed, CFG))
    assert int(packed.spill_used) == 0
    assert all(mut.validate_mutable(packed, CFG).values())
    # compact must not recycle deleted ids for later auto-assigned inserts
    assert int(packed.next_id) == int(state.next_id)


def test_rebuild_escape_hatch_matches_compact(rng):
    pts, labels = _data(rng, 600)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts, CFG, proj, labels=labels), CFG)
    state = mut.insert(state, CFG, pts[:50] + 0.01, labels=labels[:50])
    _assert_index_equal(
        mut.snapshot(mut.compact(state, CFG), CFG),
        mut.snapshot(mut.rebuild(state, CFG), CFG),
    )


def test_delete_unknown_id_strict_vs_lenient(rng):
    pts, _ = _data(rng, 100)
    state = mut.from_index(build_index(pts, CFG, identity_projection(pts)), CFG)
    with pytest.raises(KeyError, match="not live"):
        mut.delete(state, CFG, jnp.asarray([5, 9999], jnp.int32))
    ok = mut.delete(state, CFG, jnp.asarray([5, 9999], jnp.int32), strict=False)
    assert int(ok.n_live) == 99


# --------------------------------------------------- invariants + isolation --


def test_validate_invariants_on_mutated_index(rng):
    """The extended invariant set (CSR sortedness, base==offsets, pyramid
    chain, tile re-flattening) holds on a heavily mutated snapshot — and the
    tile check actually FAILS on a corrupted tile array."""
    pts, labels = _data(rng, 1000)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts, CFG, proj, labels=labels), CFG)
    state = mut.insert(state, CFG, pts[:200] * 0.5, labels=labels[:200])
    state = mut.delete(state, CFG, jnp.arange(50, 350, dtype=jnp.int32))
    snap = mut.snapshot(state, CFG)
    inv = validate_invariants(snap, CFG)
    assert all(inv.values()), inv
    bad = snap._replace(pyr_tiles=snap.pyr_tiles.at[0, 0, 0, 0].add(7))
    assert not validate_invariants(bad, CFG)["tiles_match_pyramid"]
    bad2 = snap._replace(
        pyramid=(snap.pyramid[0],) + tuple(
            p.at[0, 0, 0].add(1) for p in snap.pyramid[1:]
        )
    )
    assert not validate_invariants(bad2, CFG)["pyramid_chain_consistent"]


def test_snapshot_isolation_under_concurrent_mutation(rng):
    """A snapshot handle keeps serving the SAME results while the source
    handle keeps inserting/deleting (arrays are immutable; delta updates
    build new ones) — the mid-search corruption case from the issue."""
    pts, labels = _data(rng, 800)
    proj = identity_projection(jnp.concatenate([pts, pts * 2]))
    s = api.ActiveSearcher.from_index(
        build_index(pts, CFG, proj, labels=labels), CFG
    )
    q = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
    frozen = s.snapshot()
    want = frozen.search(q, 8)
    live = s
    for step in range(3):
        live = live.insert(pts[:100] * (1.1 + step), labels=labels[:100])
        live = live.delete(live.index.ids_sorted[:10])
        _assert_results_equal(want, frozen.search(q, 8), msg=f"step{step}")
    assert live.index.n_points == 800 + 3 * 100 - 3 * 10


def test_snapshot_state_decoupled_from_source(rng):
    """insert on a snapshot() handle does not advance the source's slack
    state, and vice versa."""
    pts, _ = _data(rng, 300)
    s = api.ActiveSearcher.from_index(
        build_index(pts, CFG, identity_projection(pts)), CFG
    )
    a = s.insert(pts[:10] + 0.01)
    frozen = a.snapshot()
    assert frozen.stats()["mutable"] is False and a.stats()["mutable"] is True
    b = frozen.insert(pts[:5] + 0.02)
    assert b.index.n_points == 315 and a.index.n_points == 310


# -------------------------------------------------------------- consumers ----


def test_retrieval_memory_online_extension_parity(rng):
    cfg = rmem.RetrievalMemoryConfig(n_retrieved=8)
    proj = rmem.make_projection(jax.random.PRNGKey(0), head_dim=16)
    keys = jnp.asarray(rng.normal(size=(512, 16)) * 0.3, jnp.float32)
    full = rmem.build_memory_index(keys, cfg, proj)
    grown = rmem.extend_memory_index(
        rmem.build_memory_index(keys[:384], cfg, proj), cfg, keys[384:]
    )
    _assert_index_equal(full, grown)
    # a query near a NEW key retrieves its (appended) position
    pos, ok = rmem.retrieve_positions(grown, cfg, keys[500:502])
    assert bool(ok.any()) and 500 in np.asarray(pos[0])


def test_knn_lm_datastore_online_extension(rng):
    cfg = knn_lm.KNNLMConfig(k=4)
    keys, _ = _data(rng, 400, d=8)
    toks = jnp.asarray(rng.integers(0, 32, size=400), jnp.int32)
    full = knn_lm.build_datastore(keys, toks, cfg)
    part = knn_lm.build_datastore(keys[:300], toks[:300], cfg,
                                  proj=full.proj)
    grown = knn_lm.extend_datastore(part, cfg, keys[300:], toks[300:])
    _assert_index_equal(full, grown)
    lp_full = knn_lm.knn_logprobs(full, cfg, keys[:6], 32)
    lp_grown = knn_lm.knn_logprobs(grown, cfg, keys[:6], 32)
    np.testing.assert_array_equal(np.asarray(lp_full), np.asarray(lp_grown))


def test_checkpoint_roundtrip_mutable_state(rng, tmp_path):
    """save_mutable_index/restore_mutable_index preserve the FULL mutation
    state — the restored index keeps accepting deltas and stays
    bit-identical to the never-persisted one."""
    pts, labels = _data(rng, 600)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts, CFG, proj, labels=labels), CFG)
    state = mut.insert(state, CFG, pts[:80] * 0.9, labels=labels[:80])
    state = mut.delete(state, CFG, jnp.arange(10, dtype=jnp.int32))

    mgr = CheckpointManager(str(tmp_path))
    mgr.save_mutable_index(3, state, blocking=True)
    mgr.wait()
    back = mgr.restore_mutable_index(3)
    _assert_index_equal(mut.snapshot(state, CFG), mut.snapshot(back, CFG))
    more = pts[100:150] * 1.05
    _assert_index_equal(
        mut.snapshot(mut.insert(state, CFG, more), CFG),
        mut.snapshot(mut.insert(back, CFG, more), CFG),
    )


# ------------------------------------------------------------------- edges ---


def test_insert_empty_batch_and_custom_ids(rng):
    pts, _ = _data(rng, 200)
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=32, r0=4,
                     k_slack=2.0)
    state = mut.from_index(build_index(pts, cfg, identity_projection(pts)), cfg)
    assert mut.insert(state, cfg, jnp.zeros((0, 2), jnp.float32)) is state
    grown = mut.insert(state, cfg, pts[:3] + 0.01,
                       ids=jnp.asarray([500, 700, 600], jnp.int32))
    assert int(grown.next_id) == 701
    snap = mut.snapshot(grown, cfg)
    assert {500, 600, 700} <= set(np.asarray(snap.ids_sorted).tolist())


def test_delete_with_colliding_ids_kills_every_carrier(rng):
    """Records are keyed by id: if a caller inserts a duplicate of a live id,
    delete(id) removes BOTH carriers and the strict check counts matched IDS
    (not slots), so it neither rejects the delete nor reports a negative
    missing count (regression)."""
    pts, _ = _data(rng, 100)
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=32, r0=4,
                     k_slack=2.0)
    state = mut.from_index(build_index(pts, cfg, identity_projection(pts)), cfg)
    state = mut.insert(state, cfg, pts[5:6] + 0.01,
                       ids=jnp.asarray([5], jnp.int32))
    state = mut.delete(state, cfg, jnp.asarray([5], jnp.int32))
    assert int(state.n_live) == 99  # 101 - both carriers of id 5
    assert 5 not in np.asarray(mut.snapshot(state, cfg).ids_sorted).tolist()
    assert all(mut.validate_mutable(state, cfg).values())


def test_insert_batch_sizes_share_jit_shapes(rng):
    """pow2 padding: batches of 5 and 7 run through the same padded kernel
    shape and still produce rebuild-identical contents."""
    pts, _ = _data(rng, 300)
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=32, r0=4,
                     k_slack=2.0)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts[:288], cfg, proj), cfg)
    state = mut.insert(state, cfg, pts[288:293])   # 5 -> padded to 8
    state = mut.insert(state, cfg, pts[293:300])   # 7 -> same padded shape
    _assert_index_equal(build_index(pts, cfg, proj),
                        mut.snapshot(state, cfg))


def test_mutable_with_sat_counter(rng):
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=32, r0=4,
                     k_slack=2.0, counter="sat")
    pts, _ = _data(rng, 400)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts[:300], cfg, proj), cfg)
    state = mut.insert(state, cfg, pts[300:])
    ref = build_index(pts, cfg, proj)
    snap = mut.snapshot(state, cfg)
    np.testing.assert_array_equal(np.asarray(ref.sat), np.asarray(snap.sat))
    assert snap.pyr_tiles is None


def test_mutation_rejected_by_capability_not_name(rng):
    """Eager validation is capability-driven: a backend registered WITHOUT
    `supports_mutation` rejects insert/delete with the capability named in
    the message, before any state is opened — same PR-3 style as the
    interpret/d_chunk plan validation."""
    from repro.core import engine

    pts, labels = _data(rng, 64)
    cfg = GridConfig(grid_size=32, tile=8, window=8, row_cap=16, r0=4)
    api.register_backend(
        "frozen-test", api.BackendImpl(search=lambda *a, **k: None),
    )
    try:
        s = api.ActiveSearcher.from_index(
            build_index(pts, cfg, identity_projection(pts), labels=labels),
            cfg,
        ).with_plan(backend="frozen-test")
        assert not api.get_backend("frozen-test").supports_mutation
        with pytest.raises(ValueError, match="supports_mutation"):
            s.insert(pts[:2])
        with pytest.raises(ValueError, match="supports_mutation"):
            s.delete(jnp.asarray([0], jnp.int32))
        # the error lists the capable backends, so the fix is in the message
        with pytest.raises(ValueError, match="sharded"):
            s.insert(pts[:2])
    finally:
        engine._REGISTRY.pop("frozen-test", None)


def test_sharded_merge_tiebreak_pinned_to_global_id(rng):
    """Regression pin for the global top-k merge: distance ties order by
    GLOBAL id (lax.sort num_keys=2), not by shard/CSR position — the full
    multi-shard version lives in tests/test_sharded_mutable.py."""
    cfg = GridConfig(grid_size=32, tile=8, window=16, row_cap=16, r0=4)
    pts = jnp.asarray([[0.5, 0.0], [-0.5, 0.0], [4.0, 4.0], [-4.0, -4.0]],
                      jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("data",))
    s = api.ActiveSearcher.build_sharded(
        pts, mesh=mesh, axis="data", cfg=cfg,
        proj=identity_projection(pts),
        ids=jnp.asarray([3, 7, 11, 12], jnp.int32),  # CSR order is 7 then 3
    )
    from repro.core import distributed as D

    res = s.search(D.replicate_queries(jnp.zeros((1, 2), jnp.float32), mesh), 2)
    d = np.asarray(res.dists[0])
    assert d[0] == d[1], d
    np.testing.assert_array_equal(np.asarray(res.ids[0]), [3, 7])
