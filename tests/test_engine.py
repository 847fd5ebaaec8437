"""The ActiveSearcher facade (core/engine.py, exported as repro.api):
backend registry, ExecutionPlan validation, parity with the pre-facade
entry points, deprecation shims, and the B=0 run_chunked regression."""

import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import active_search as act
from repro.core import exact
from repro.core.active_search import run_chunked
from repro.core.grid import GridConfig, build_index
from repro.core.projection import identity_projection
from repro.kernels.csr_candidate_topk import rows_in_flight


def _searcher(rng, n=1000, n_classes=3, **kw):
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, max(n_classes, 1), size=n), jnp.int32)
    cfg = GridConfig(grid_size=128, tile=16, n_classes=n_classes, window=48,
                     row_cap=48, r0=8, k_slack=2.0, **kw)
    idx = build_index(pts, cfg, identity_projection(pts), labels=labels)
    return pts, labels, api.ActiveSearcher.from_index(idx, cfg)


def _assert_results_equal(a, b):
    for field in api.SearchResult._fields:
        ga, gb = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert ga.shape == gb.shape, (field, ga.shape, gb.shape)
        assert ga.dtype == gb.dtype, (field, ga.dtype, gb.dtype)
        np.testing.assert_array_equal(ga, gb, err_msg=field)


# ------------------------------------------------------------------ parity ---


@pytest.mark.parametrize("mode", ["refined", "paper"])
def test_facade_parity_jnp_vs_pallas(rng, mode):
    """The handle is bit-identical to the pre-facade paths: the jnp plan
    reproduces _search_jnp, the pallas plan reproduces core.batched, and the
    two plans agree with each other — search AND classify, both modes."""
    _, _, s = _searcher(rng)
    q = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
    ref = act._search_jnp(s.index, s.cfg, q, 8, mode)
    got = s.search(q, 8, mode=mode)
    _assert_results_equal(ref, got)
    got_p = s.with_plan(backend="pallas").search(q, 8, mode=mode)
    _assert_results_equal(ref, got_p)
    np.testing.assert_array_equal(
        np.asarray(s.classify(q, 8, mode=mode)),
        np.asarray(s.with_plan(backend="pallas").classify(q, 8, mode=mode)),
    )


def test_facade_parity_exact(rng):
    """The exact backend folds ExactResult into SearchResult: same ids and
    distances as the raw comparator (original point order), paper-stat
    fields defaulted, classify bit-identical to exact.classify."""
    pts, labels, s = _searcher(rng)
    q = jnp.asarray(rng.normal(size=(6, 2)), jnp.float32)
    raw = exact.knn(q, pts, 8, metric=s.cfg.metric)
    got = s.with_plan(backend="exact").search(q, 8)
    np.testing.assert_array_equal(np.asarray(raw.ids), np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(raw.dists), np.asarray(got.dists))
    assert got.labels.shape == got.ids.shape
    np.testing.assert_array_equal(
        np.asarray(got.labels),
        np.asarray(labels)[np.asarray(raw.ids)],
    )
    # paper-stat fields are defaulted, batched, and well-typed
    assert got.radius.shape == (6,) and int(np.asarray(got.radius).max()) == 0
    assert bool(np.asarray(got.converged).all())
    assert not bool(np.asarray(got.truncated).any())
    np.testing.assert_array_equal(
        np.asarray(exact.classify(q, pts, labels, 8, 3)),
        np.asarray(s.with_plan(backend="exact").classify(q, 8)),
    )


def test_count_at_parity_across_backends(rng):
    """count_at: jnp (vmap count_in_circle) == pallas == pallas_q8 (both the
    level-scheduled kernel) for radii spanning levels."""
    _, _, s = _searcher(rng)
    q = jnp.asarray(rng.normal(size=(10, 2)), jnp.float32)
    radii = jnp.asarray(rng.integers(1, s.cfg.max_radius, size=10), jnp.int32)
    want = s.count_at(q, radii)
    for backend in ("pallas", "pallas_q8"):
        got = s.with_plan(backend=backend).count_at(q, radii)
        np.testing.assert_array_equal(
            np.asarray(want), np.asarray(got), err_msg=backend
        )


# ---------------------------------------------------------------- registry ---


def test_unknown_backend_lists_registered_names(rng):
    _, _, s = _searcher(rng)
    q = jnp.zeros((1, 2), jnp.float32)
    with pytest.raises(ValueError, match=r"unknown backend 'tpu-magic'"):
        s.with_plan(backend="tpu-magic").search(q, 3)
    with pytest.raises(ValueError, match=r"'jnp'.*'pallas'"):
        s.with_plan(backend="tpu-magic").search(q, 3)


def test_register_backend_roundtrip(rng):
    """A custom BackendImpl registered under a new name is dispatched by the
    facade with the searcher handle and the call arguments intact."""
    _, _, s = _searcher(rng)
    q = jnp.zeros((2, 2), jnp.float32)
    seen = {}

    def fake_search(searcher, queries, k, mode):
        seen["cfg"] = searcher.cfg
        seen["k"], seen["mode"] = k, mode
        return act._search_jnp(searcher.index, searcher.cfg, queries, k, mode)

    api.register_backend("custom-test", api.BackendImpl(search=fake_search))
    try:
        assert "custom-test" in api.registered_backends()
        got = s.with_plan(backend="custom-test").search(q, 3, mode="paper")
        assert seen == {"cfg": s.cfg, "k": 3, "mode": "paper"}
        _assert_results_equal(act._search_jnp(s.index, s.cfg, q, 3, "paper"), got)
        # ops the impl does not provide raise eagerly, naming the backend
        with pytest.raises(ValueError, match="custom-test.*classify"):
            s.with_plan(backend="custom-test").classify(q, 3)
    finally:
        from repro.core import engine

        engine._REGISTRY.pop("custom-test", None)
    with pytest.raises(TypeError, match="BackendImpl"):
        api.register_backend("bad", lambda *a: None)


# ------------------------------------------------------------------- shims ---


def test_deprecated_shims_warn_and_match_facade(rng):
    _, _, s = _searcher(rng)
    q = jnp.asarray(rng.normal(size=(4, 2)), jnp.float32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        shim_res = act.search(s.index, s.cfg, q, 5, backend="pallas")
        shim_cls = act.classify(s.index, s.cfg, q, 5)
    assert sum(issubclass(x.category, DeprecationWarning) for x in w) == 2
    _assert_results_equal(s.with_plan(backend="pallas").search(q, 5), shim_res)
    np.testing.assert_array_equal(
        np.asarray(s.classify(q, 5)), np.asarray(shim_cls)
    )


# -------------------------------------------------------- eager validation ---


@pytest.mark.parametrize("backend", ["jnp", "pallas", "exact"])
def test_classify_without_classes_raises_uniformly(rng, backend):
    _, _, s = _searcher(rng, n=300, n_classes=0)
    q = jnp.zeros((2, 2), jnp.float32)
    with pytest.raises(ValueError, match="n_classes > 0"):
        s.with_plan(backend=backend).classify(q, 3)


@pytest.mark.parametrize("backend", ["jnp", "exact"])
def test_interpret_rejected_uniformly_off_pallas(rng, backend):
    _, _, s = _searcher(rng, n=300)
    q = jnp.zeros((2, 2), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        s.with_plan(backend=backend, interpret=True).search(q, 3)
    with pytest.raises(ValueError, match="interpret"):
        s.with_plan(backend=backend, interpret=False).classify(q, 3)


def test_sharded_takes_the_pallas_knobs(rng):
    """The sharded backend runs the pallas kernels on every shard, so it
    takes their plan knobs: interpret and d_chunk pass validation and
    survive a with_plan switch to it."""
    impl = api.get_backend("sharded")
    assert impl.supports_interpret and impl.supports_d_chunk
    _, _, s = _searcher(rng, n=300)
    p = s.with_plan(backend="pallas", interpret=True, d_chunk=8)
    sh = p.with_plan(backend="sharded")
    assert sh.plan.interpret is True and sh.plan.d_chunk == 8
    assert sh.check_plan() is impl


def test_interpret_resolves_by_backend_and_is_refused_on_tpu(rng, monkeypatch):
    """interpret=None is the interpreter on the CPU backend and Mosaic on a
    TPU; an explicit interpret=True is refused on a TPU by the facade and by
    the serving queue, before anything is traced."""
    from repro.kernels import ops
    from repro.launch.serve import DynamicBatcher

    assert ops.resolve_interpret(None) is True  # the tests run on the CPU
    _, _, s = _searcher(rng, n=300)
    q = jnp.zeros((2, 2), jnp.float32)
    forced = s.with_plan(backend="pallas", interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    assert ops.resolve_interpret(True) is True
    with pytest.raises(ValueError, match="interpreter on a TPU"):
        forced.search(q, 3)
    with pytest.raises(ValueError, match="interpreter on a TPU"):
        forced.count_at(q, jnp.ones((2,), jnp.int32))
    with pytest.raises(ValueError, match="interpreter on a TPU"):
        DynamicBatcher(forced, k=3)
    DynamicBatcher(s.with_plan(backend="pallas"), k=3)  # the default is served


def test_exact_pairwise_at_highest_is_bit_identical_on_cpu(rng):
    """The exact reference runs its matmul at Precision.HIGHEST (full
    float32 on a TPU); on the CPU that changes no bit of the answer."""
    q = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)

    def default_precision(q, x):
        qq = jnp.sum(q * q, axis=-1, keepdims=True)
        xx = jnp.sum(x * x, axis=-1)
        return jnp.sqrt(jnp.maximum(qq - 2.0 * (q @ x.T) + xx[None, :], 0.0))

    np.testing.assert_array_equal(
        np.asarray(jax.jit(exact._pairwise, static_argnums=2)(q, x, "l2")),
        np.asarray(jax.jit(default_precision)(q, x)),
    )


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set;
    otherwise the cache sits at the fixed <repo>/.jax_cache."""
    from pathlib import Path

    from repro.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = cc.enable_compile_cache()
        assert got == Path(__file__).resolve().parents[1] / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(got)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    (tmp_path / "a").write_text("x")
    assert cc.cache_entries(tmp_path) == 1
    assert cc.cache_entries(tmp_path / "missing") == 0


def test_plan_validation(rng):
    with pytest.raises(ValueError, match="chunk_size"):
        api.ExecutionPlan(chunk_size=0)
    with pytest.raises(ValueError, match="donate"):
        api.ExecutionPlan(donate=True)
    _, _, s = _searcher(rng, n=200)
    with pytest.raises(ValueError, match="mode"):
        s.search(jnp.zeros((1, 2), jnp.float32), 3, mode="telepathic")
    with pytest.raises(ValueError, match="full ExecutionPlan OR"):
        s.with_plan(api.ExecutionPlan(), backend="pallas")


def test_gridconfig_rejects_unknown_metric():
    with pytest.raises(ValueError, match="metric"):
        GridConfig(metric="cosine")
    with pytest.raises(ValueError, match="counter"):
        GridConfig(counter="hyperloglog")
    GridConfig(metric="l1")  # both paper metrics still construct
    GridConfig(metric="l2")


# -------------------------------------------------------------- B=0 batches --


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_empty_batch_with_chunking(rng, backend):
    """Regression: B=0 with chunk_size set must return empty, correctly
    shaped pytrees instead of tripping the pad-by-last-row broadcast or
    invoking a kernel on a zero-size grid."""
    _, _, s = _searcher(rng, n=300)
    s = s.with_plan(backend=backend, chunk_size=4)
    empty = jnp.zeros((0, 2), jnp.float32)
    res = s.search(empty, 5)
    assert res.ids.shape == (0, 5) and res.ids.dtype == jnp.int32
    assert res.dists.shape == (0, 5) and res.dists.dtype == jnp.float32
    assert res.radius.shape == (0,) and res.valid.dtype == bool
    cls = s.classify(empty, 5)
    assert cls.shape == (0,) and cls.dtype == jnp.int32


def test_run_chunked_empty_direct():
    out = run_chunked(
        lambda q: {"x": q * 2.0, "n": jnp.sum(q, axis=1)},
        jnp.zeros((0, 3), jnp.float32),
        chunk_size=8,
    )
    assert out["x"].shape == (0, 3) and out["n"].shape == (0,)


# ------------------------------------------------------------------- misc ----


def test_with_plan_and_stats(rng):
    _, _, s = _searcher(rng, n=400)
    s2 = s.with_plan(backend="pallas", chunk_size=16)
    assert s2.plan == api.ExecutionPlan(backend="pallas", chunk_size=16)
    assert s2.index is s.index and s.plan.backend == "jnp"  # original untouched
    st = s2.stats()
    assert st["n_points"] == 400 and st["backend"] == "pallas"
    assert st["csr_bytes"] > 0 and st["pyr_tiles_bytes"] > 0
    assert st["levels"] == s.cfg.levels


def test_stats_candidate_rows_in_flight(rng):
    """stats() reports the depth of the candidate kernel's row pipeline for
    the handle's (window, row_cap, dim)."""
    pts = jnp.asarray(rng.normal(size=(300, 8)), jnp.float32)
    cfg = GridConfig(grid_size=128, tile=16, window=8, row_cap=16, r0=8)
    s = api.ActiveSearcher.build(pts, cfg=cfg,
                                 plan=api.ExecutionPlan(backend="pallas"))
    assert s.stats()["candidate_rows_in_flight"] == rows_in_flight(8, 16, 8)


def test_build_defaults_to_pca_projection(rng):
    pts = jnp.asarray(rng.normal(size=(500, 8)), jnp.float32)
    s = api.ActiveSearcher.build(pts, cfg=GridConfig(grid_size=128, tile=16,
                                                     window=32, row_cap=32,
                                                     r0=8, k_slack=2.0))
    q = pts[:4]
    res = s.search(q, 5)
    assert res.ids.shape == (4, 5)
    # a stored point must find itself as its own nearest neighbor
    np.testing.assert_array_equal(np.asarray(res.ids[:, 0]), np.arange(4))


def test_chunked_facade_parity(rng):
    _, _, s = _searcher(rng, n=600)
    q = jnp.asarray(rng.normal(size=(10, 2)), jnp.float32)
    full = s.search(q, 5)
    chunked = s.with_plan(chunk_size=3).search(q, 5)
    _assert_results_equal(full, chunked)


def test_count_at_respects_chunking_and_empty(rng):
    """count_at streams (q_grid, radius) PAIRS through plan.chunk_size —
    bit-identical to the unchunked call — and returns an empty (0, C)
    result for an empty batch instead of reaching a kernel."""
    _, _, s = _searcher(rng, n=500)
    q = jnp.asarray(rng.normal(size=(7, 2)), jnp.float32)
    radii = jnp.asarray(rng.integers(1, s.cfg.max_radius, size=7), jnp.int32)
    full = s.count_at(q, radii)
    chunked = s.with_plan(chunk_size=3).count_at(q, radii)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(chunked))
    empty = s.with_plan(chunk_size=3).count_at(
        jnp.zeros((0, 2), jnp.float32), jnp.zeros((0,), jnp.int32)
    )
    assert empty.shape == (0, s.cfg.n_channels)


def test_exact_ordered_cached_on_handle(rng):
    """The exact backend's restored-order arrays are computed once per
    handle, not once per call."""
    _, _, s = _searcher(rng, n=400)
    e = s.with_plan(backend="exact")
    q = jnp.asarray(rng.normal(size=(3, 2)), jnp.float32)
    first = e.search(q, 4)
    cache = e.__dict__.get("_exact_ordered_cache")
    assert cache is not None
    second = e.search(q, 4)
    assert e.__dict__["_exact_ordered_cache"] is cache  # reused, not rebuilt
    _assert_results_equal(first, second)


def test_exact_cache_does_not_leak_tracers(rng):
    """Regression: memoizing the reorder while tracing (closed-over handle
    under jit, or the B=0 eval_shape probe) must not store tracers on the
    handle — later calls would die with UnexpectedTracerError."""
    _, _, s = _searcher(rng, n=300)
    e = s.with_plan(backend="exact")
    f = jax.jit(lambda q: e.search(q, 4).ids)
    assert f(jnp.zeros((3, 2), jnp.float32)).shape == (3, 4)
    assert f(jnp.zeros((7, 2), jnp.float32)).shape == (7, 4)  # retrace, reuse handle
    e2 = s.with_plan(backend="exact", chunk_size=4)
    e2.search(jnp.zeros((0, 2), jnp.float32), 4)  # eval_shape probe path
    res = e2.search(jnp.zeros((2, 2), jnp.float32), 4)  # must not crash
    assert res.ids.shape == (2, 4)


def test_with_plan_backend_switch_drops_interpret(rng):
    """Switching backends via with_plan clears the Pallas-only interpret
    knob instead of tripping validation (explicit interpret= still wins)."""
    _, _, s = _searcher(rng, n=300)
    p = s.with_plan(backend="pallas", interpret=True)
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    res = p.with_plan(backend="exact").search(q, 3)  # must not raise
    assert res.ids.shape == (2, 3)
    assert p.with_plan(backend="jnp").plan.interpret is None
    assert p.with_plan(backend="pallas_q8").plan.interpret is True
    with pytest.raises(ValueError, match="interpret"):
        p.with_plan(backend="exact", interpret=True).search(q, 3)


def test_d_chunk_plan_validation(rng):
    """d_chunk is eager and uniform like interpret: positive-only at plan
    construction, Pallas candidate-ranking backends only at dispatch, and
    with_plan backend switches drop the now-illegal knob."""
    _, _, s = _searcher(rng, n=300)
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    for bad in (0, -4):
        with pytest.raises(ValueError, match="d_chunk"):
            api.ExecutionPlan(d_chunk=bad)
    for backend in ("jnp", "exact"):
        with pytest.raises(ValueError, match="d_chunk"):
            s.with_plan(backend=backend, d_chunk=8).search(q, 3)
    # nor does count_at on a backend that never ranks candidates
    with pytest.raises(ValueError, match="d_chunk"):
        s.with_plan(backend="jnp", d_chunk=8).count_at(
            q, jnp.ones((2,), jnp.int32)
        )
    p = s.with_plan(backend="pallas", d_chunk=8)
    assert p.search(q, 3).ids.shape == (2, 3)
    assert p.with_plan(backend="exact").plan.d_chunk is None  # dropped
    assert p.with_plan(backend="pallas_q8").plan.d_chunk == 8  # kept


def test_adaptive_r0_plan_validation(rng):
    """adaptive_r0 is gated like interpret/d_chunk: only backends that run
    the Eq.-1 radius loop accept it, with_plan backend switches drop the
    now-illegal knob, and an explicit override still wins."""
    _, _, s = _searcher(rng, n=300)
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    assert not api.get_backend("exact").supports_adaptive_r0
    with pytest.raises(ValueError, match="adaptive_r0"):
        s.with_plan(backend="exact", adaptive_r0=True)._impl("search")
    for backend in ("jnp", "pallas", "pallas_q8", "sharded"):
        assert api.get_backend(backend).supports_adaptive_r0, backend
    p = s.with_plan(backend="pallas", adaptive_r0=True)
    assert p.search(q, 3).ids.shape == (2, 3)
    assert p.with_plan(backend="exact").plan.adaptive_r0 is False  # dropped
    assert p.with_plan(backend="jnp").plan.adaptive_r0 is True     # kept
    with pytest.raises(ValueError, match="adaptive_r0"):
        p.with_plan(backend="exact", adaptive_r0=True).search(q, 3)


def test_rerank_k_plan_validation(rng):
    """rerank_k is gated like d_chunk: positive-only at plan construction,
    quantized-candidate backends only at dispatch (supports_quantized),
    rerank_k >= k at the search call where k is known, and with_plan
    backend switches drop the now-illegal knob."""
    _, _, s = _searcher(rng, n=300)
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    for bad in (0, -4):
        with pytest.raises(ValueError, match="rerank_k"):
            api.ExecutionPlan(rerank_k=bad)
    for backend in ("jnp", "pallas", "exact", "sharded"):
        assert not api.get_backend(backend).supports_quantized
        with pytest.raises(ValueError, match="rerank_k"):
            s.with_plan(backend=backend, rerank_k=8).search(q, 3)
    assert api.get_backend("pallas_q8").supports_quantized
    # a shortlist shallower than k can never return k exact rows
    with pytest.raises(ValueError, match="rerank_k"):
        s.with_plan(backend="pallas_q8", rerank_k=2).search(q, 3)
    p = s.with_plan(backend="pallas_q8", rerank_k=8)
    assert p.search(q, 3).ids.shape == (2, 3)
    assert p.with_plan(backend="pallas").plan.rerank_k is None  # dropped
    assert p.with_plan(backend="pallas_q8", chunk_size=2).plan.rerank_k == 8


@pytest.mark.parametrize("mode", ["refined", "paper"])
def test_adaptive_r0_parity_across_backends(rng, mode):
    """ISSUE-6 acceptance: with adaptive_r0=True every registered backend
    returns the SAME SearchResult as the jnp oracle — ids/dists AND the
    Eq.-1 stat fields (radius/count/iters/converged), both modes."""
    _, _, s = _searcher(rng)
    q = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
    ref = act._search_jnp(s.index, s.cfg, q, 8, mode, adaptive_r0=True)
    for backend in ("jnp", "pallas"):
        got = s.with_plan(backend=backend, adaptive_r0=True).search(
            q, 8, mode=mode
        )
        _assert_results_equal(ref, got)
        np.testing.assert_array_equal(
            np.asarray(s.with_plan(adaptive_r0=True).classify(q, 8, mode=mode)),
            np.asarray(s.with_plan(backend=backend, adaptive_r0=True)
                       .classify(q, 8, mode=mode)),
            err_msg=backend,
        )


def test_from_index_upgrades_pre_layout_tiles(rng):
    """A pre-layout index (pyr_tiles=None) is upgraded ONCE by from_index;
    the pallas count path refuses to re-flatten per call."""
    from repro.core import batched
    from repro.core.grid import flatten_pyramid_tiles

    pts, labels, s = _searcher(rng, n=400)
    stripped = s.index._replace(pyr_tiles=None)
    up = api.ActiveSearcher.from_index(stripped, s.cfg)
    assert up.index.pyr_tiles is not None
    np.testing.assert_array_equal(
        np.asarray(up.index.pyr_tiles),
        np.asarray(flatten_pyramid_tiles(stripped.pyramid, s.cfg.tile)),
    )
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    _assert_results_equal(
        up.with_plan(backend="pallas").search(q, 3),
        s.with_plan(backend="pallas").search(q, 3),
    )
    # reaching the kernels with a pre-layout index is a hard error now
    with pytest.raises(ValueError, match="pyr_tiles"):
        batched.batched_counts(
            stripped, s.cfg, jnp.zeros((1, 2), jnp.float32),
            jnp.ones((1,), jnp.int32),
        )


# ------------------------------------------------------ mutation capability --


def test_supports_mutation_capability_flags():
    """Every built-in backend can serve a refreshed post-mutation snapshot
    and declares supports_mutation; a BackendImpl does not by default."""
    assert api.registered_backends() == (
        "exact", "jnp", "pallas", "pallas_q8", "sharded")
    for name in api.registered_backends():
        assert api.get_backend(name).supports_mutation, name
    assert not api.BackendImpl().supports_mutation


def test_serve_knn_online_rejects_non_mutation_backend(monkeypatch):
    """lm_serve.py --knn-online validates by CAPABILITY before model init: a
    searchable backend without supports_mutation exits naming the flag and
    the capable alternatives — no name-matching, no late failure."""
    from repro.core import engine
    from repro.launch import lm_serve

    api.register_backend(
        "searchonly-test",
        api.BackendImpl(search=lambda *a, **k: None),
    )
    try:
        monkeypatch.setattr(
            "sys.argv",
            ["lm_serve", "--knn", "--knn-online",
             "--knn-backend", "searchonly-test"],
        )
        with pytest.raises(SystemExit, match="supports_mutation") as e:
            lm_serve.main()
        assert "jnp" in str(e.value)  # the fix is in the message
    finally:
        engine._REGISTRY.pop("searchonly-test", None)


def test_serving_queue_imports_no_lm_stack():
    """The index's serving queue stands apart from the kNN-LM stack:
    importing `launch/serve.py` loads no model, config, optimizer,
    parallelism or step-factory module."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = (
        "import sys, repro.launch.serve\n"
        "print(sorted(m for m in sys.modules if m.startswith(("
        "'repro.models', 'repro.configs', 'repro.optim', "
        "'repro.parallel', 'repro.launch.steps'))))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, timeout=120, env={**os.environ, "PYTHONPATH": src},
    )
    assert out.stdout.strip() == "[]", out.stdout
