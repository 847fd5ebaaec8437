"""backend="pallas" (core/batched.py, interpret-mode kernels) vs the jnp
reference path: SearchResult parity must be bit-for-bit in refined mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import active_search as act
from repro.core import batched
from repro.core.grid import GridConfig, build_index
from repro.core.projection import identity_projection


def _index(rng, n=1200, n_classes=3, metric="l2", grid=128, **kw):
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, max(n_classes, 1), size=n), jnp.int32)
    cfg = GridConfig(grid_size=grid, tile=16, n_classes=n_classes, window=48,
                     row_cap=48, r0=8, k_slack=2.0, metric=metric, **kw)
    return pts, labels, cfg, build_index(
        pts, cfg, identity_projection(pts), labels=labels
    )


def _assert_results_equal(a: act.SearchResult, b: act.SearchResult):
    for field in act.SearchResult._fields:
        ga = np.asarray(getattr(a, field))
        gb = np.asarray(getattr(b, field))
        assert ga.shape == gb.shape, (field, ga.shape, gb.shape)
        assert ga.dtype == gb.dtype, (field, ga.dtype, gb.dtype)
        np.testing.assert_array_equal(ga, gb, err_msg=field)


def test_refined_parity_quick(rng):
    """Fast-tier parity: one index per metric, k swept inside the test so the
    interpret-mode pipeline compiles a minimal number of variants.  The
    fused csr_candidate_topk pipeline ("pallas") must be bit-identical to
    the jnp reference."""
    for metric in ("l2", "l1"):
        _, _, cfg, idx = _index(rng, metric=metric)
        q = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
        for k in (1, 8):
            ref = act.search(idx, cfg, q, k, backend="jnp")
            got = act.search(idx, cfg, q, k, backend="pallas")
            _assert_results_equal(ref, got)


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 8, 64])
@pytest.mark.parametrize("metric", ["l2", "l1"])
@pytest.mark.parametrize("b", [1, 32])
def test_refined_parity_bitforbit(rng, k, metric, b):
    """The full sweep the issue asks for: every (B, metric, k) combination
    bit-for-bit.  Each combo costs seconds of interpret-mode emulation, so
    the sweep rides in the full tier; test_refined_parity_quick keeps a
    representative subset in the fast tier."""
    _, _, cfg, idx = _index(rng, metric=metric)
    q = jnp.asarray(rng.normal(size=(b, 2)), jnp.float32)
    ref = act.search(idx, cfg, q, k, backend="jnp")
    got = act.search(idx, cfg, q, k, backend="pallas")
    _assert_results_equal(ref, got)


@pytest.mark.parametrize("k", [1, 11])
def test_paper_mode_parity(rng, k):
    """Paper mode ranks cell centers inside the final circle: the fused
    kernel's center_cells+radii path must reproduce the jnp reference
    bit-for-bit."""
    _, _, cfg, idx = _index(rng)
    q = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    ref = act.search(idx, cfg, q, k, mode="paper", backend="jnp")
    got = act.search(idx, cfg, q, k, mode="paper", backend="pallas")
    _assert_results_equal(ref, got)


@pytest.mark.parametrize("mode,k,n,b", [
    ("refined", 11, 2500, 40), ("paper", 11, 2500, 40),
    ("refined", 9, 2000, 24),
])
def test_classify_parity(rng, mode, k, n, b):
    """classify threads _search_impl and the count fallback through the
    kernels: the pallas votes equal the jnp path's."""
    _, _, cfg, idx = _index(rng, n=n)
    q = jnp.asarray(rng.normal(size=(b, 2)), jnp.float32)
    ref = act.classify(idx, cfg, q, k, mode=mode, backend="jnp")
    got = act.classify(idx, cfg, q, k, mode=mode, backend="pallas")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_parity_k_exceeds_candidate_window(rng):
    """k > window*row_cap valid candidates: both paths pad with -1/inf."""
    pts = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    cfg = GridConfig(grid_size=128, tile=16, window=8, row_cap=8, r0=4,
                     k_slack=2.0)
    idx = build_index(pts, cfg, identity_projection(pts))
    q = jnp.asarray(rng.normal(size=(4, 2)), jnp.float32)
    ref = act.search(idx, cfg, q, 100, backend="jnp")
    got = act.search(idx, cfg, q, 100, backend="pallas")
    _assert_results_equal(ref, got)
    assert not bool(np.asarray(ref.valid).all())  # some slots really padded


def test_parity_truncated_flag(rng):
    pts = jnp.asarray(rng.normal(size=(500, 2)), jnp.float32)
    cfg = GridConfig(grid_size=256, tile=16, window=8, row_cap=8, r0=4,
                     k_slack=1.5)
    idx = build_index(pts, cfg, identity_projection(pts))
    q = jnp.zeros((2, 2), jnp.float32)
    ref = act.search(idx, cfg, q, 200, backend="jnp")
    got = act.search(idx, cfg, q, 200, backend="pallas")
    _assert_results_equal(ref, got)
    assert bool(np.asarray(got.truncated).all())


def test_parity_sat_counter(rng):
    """counter="sat" routes the batched radius loop through the integral
    image instead of tile_count_multilevel; results still match the jnp
    path."""
    _, _, cfg, idx = _index(rng, n=800, counter="sat")
    q = jnp.asarray(rng.normal(size=(8, 2)), jnp.float32)
    ref = act.search(idx, cfg, q, 7, backend="jnp")
    got = act.search(idx, cfg, q, 7, backend="pallas")
    _assert_results_equal(ref, got)


def test_batched_counts_match_scalar(rng):
    """The level-scheduled batched counts == per-query pyramid counts."""
    from repro.core import projection as proj_lib
    from repro.core import pyramid as pyr
    import jax

    pts, _, cfg, idx = _index(rng, n=1200)
    q = jnp.asarray(rng.normal(size=(16, 2)), jnp.float32)
    qg = proj_lib.to_grid_coords(idx.proj, q, cfg.grid_size)
    radii = jnp.asarray(rng.integers(1, cfg.max_radius, size=16), jnp.int32)
    got = batched.batched_counts(idx, cfg, qg, radii)
    want = jax.vmap(lambda g, r: pyr.count_in_circle(idx, cfg, g, r))(qg, radii)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_multilevel_matches_jnp_counts(rng, metric):
    """ONE tile_count_multilevel call == the jnp count path (vmapped
    pyramid.count_in_circle), radii spanning every level (including
    r == max_radius, where level selection clamps at the top of the
    pyramid)."""
    from repro.core import projection as proj_lib
    from repro.core import pyramid as pyr
    import jax

    _, _, cfg, idx = _index(rng, n=1500, metric=metric)
    assert cfg.levels >= 3  # the regime the level scheduler targets
    q = jnp.asarray(rng.normal(size=(24, 2)), jnp.float32)
    qg = proj_lib.to_grid_coords(idx.proj, q, cfg.grid_size)
    radii = jnp.concatenate([
        jnp.asarray(rng.integers(1, cfg.max_radius, size=20), jnp.int32),
        jnp.full((4,), cfg.max_radius, jnp.int32),
    ])
    got = batched.batched_counts(idx, cfg, qg, radii)
    want = jax.vmap(lambda g, r: pyr.count_in_circle(idx, cfg, g, r))(qg, radii)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_parity_grid_corner_queries(rng):
    """Backend parity where the count window clamps on both axes: queries
    pinned to the grid corners (far outside the data mass, so Eq. 1 drives
    radii up into clamped-window territory)."""
    pts, _, cfg, idx = _index(rng, n=600)
    lo, hi = float(jnp.min(pts)) - 1.0, float(jnp.max(pts)) + 1.0
    q = jnp.asarray(
        [[lo, lo], [hi, hi], [lo, hi], [hi, lo], [lo, 0.0], [0.0, hi]],
        jnp.float32,
    )
    ref_res = act.search(idx, cfg, q, 8, backend="jnp")
    got = act.search(idx, cfg, q, 8, backend="pallas")
    _assert_results_equal(ref_res, got)


def test_parity_max_radius_counts(rng):
    """r == max_radius: the level clamps to the top of the pyramid and the
    circle overruns the (whole-level) window — counts must still match the
    per-query oracle bit-for-bit."""
    from repro.core import projection as proj_lib
    from repro.core import pyramid as pyr
    import jax

    _, _, cfg, idx = _index(rng, n=900)
    q = jnp.asarray(rng.normal(size=(6, 2)), jnp.float32)
    qg = proj_lib.to_grid_coords(idx.proj, q, cfg.grid_size)
    radii = jnp.full((6,), cfg.max_radius, jnp.int32)
    got = batched.batched_counts(idx, cfg, qg, radii)
    want = jax.vmap(lambda g, r: pyr.count_in_circle(idx, cfg, g, r))(qg, radii)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # every point of the index is inside the max-radius circle
    assert int(np.asarray(got).sum()) > 0


def test_chunked_parity(rng):
    """chunk_size streams fixed-shape invocations; results are bit-identical
    for any chunking, on both backends (incl. a non-dividing chunk size)."""
    _, _, cfg, idx = _index(rng, n=800)
    q = jnp.asarray(rng.normal(size=(10, 2)), jnp.float32)
    for backend in ("jnp", "pallas"):
        full = act.search(idx, cfg, q, 5, backend=backend)
        chunked = act.search(idx, cfg, q, 5, backend=backend, chunk_size=4)
        _assert_results_equal(full, chunked)
    ref_cls = act.classify(idx, cfg, q, 5, backend="pallas")
    got_cls = act.classify(idx, cfg, q, 5, backend="pallas", chunk_size=3)
    np.testing.assert_array_equal(np.asarray(ref_cls), np.asarray(got_cls))
    for bad in (0, -1):
        with pytest.raises(ValueError, match="chunk_size"):
            act.search(idx, cfg, q, 5, backend="pallas", chunk_size=bad)


def test_interpret_threading(rng):
    """interpret= reaches the kernels from the public API (pallas backend)
    and is rejected on the jnp backend where it has no meaning."""
    _, _, cfg, idx = _index(rng, n=400)
    q = jnp.asarray(rng.normal(size=(4, 2)), jnp.float32)
    expl = act.search(idx, cfg, q, 3, backend="pallas", interpret=True)
    dflt = act.search(idx, cfg, q, 3, backend="pallas")  # env default (CPU: on)
    _assert_results_equal(expl, dflt)
    with pytest.raises(ValueError, match="interpret"):
        act.search(idx, cfg, q, 3, backend="jnp", interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        act.classify(idx, cfg, q, 3, backend="jnp", interpret=False)


def test_truncated_row_overflow_parity(rng):
    """truncated must ALSO fire when a window row holds more than row_cap
    points (candidates silently dropped by the row_cap slice) even though
    the circle fits the window — same flag from the jnp and pallas
    paths."""
    # everything in a handful of cells -> one window row overflows a tiny
    # row_cap while Eq. 1 converges at a small radius
    pts = jnp.asarray(rng.normal(size=(300, 2)) * 0.01, jnp.float32)
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=4, r0=2,
                     k_slack=4.0)
    idx = build_index(pts, cfg, identity_projection(pts))
    q = jnp.zeros((3, 2), jnp.float32)
    ref = act.search(idx, cfg, q, 3, backend="jnp")
    assert bool(np.asarray(ref.truncated).all())
    # the overflow is the ONLY trigger here: the circle itself fits
    assert bool((2 * np.asarray(ref.radius) + 1 <= cfg.window).all())
    got = act.search(idx, cfg, q, 3, backend="pallas")
    _assert_results_equal(ref, got)


def test_d_chunk_threading(rng):
    """ExecutionPlan.d_chunk reaches the candidate kernel: results stay
    correct (allclose dists, same neighbor ids as the default single-sum
    plan) for caps smaller than d, and a cap >= d is bit-identical."""
    from repro import api

    pts = jnp.asarray(rng.normal(size=(900, 8)), jnp.float32)
    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=16, r0=4,
                     k_slack=2.0)
    s = api.ActiveSearcher.build(pts, cfg=cfg)
    q = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    base = s.with_plan(backend="pallas").search(q, 5)
    for dc in (3, 8, 512):
        got = s.with_plan(backend="pallas", d_chunk=dc).search(q, 5)
        np.testing.assert_array_equal(
            np.asarray(base.ids), np.asarray(got.ids), err_msg=f"d_chunk={dc}",
        )
        np.testing.assert_allclose(
            np.asarray(base.dists), np.asarray(got.dists),
            rtol=1e-5, atol=1e-6, err_msg=f"d_chunk={dc}",
        )
        if dc >= 8:
            _assert_results_equal(base, got)


def test_unknown_candidate_pipeline_raises(rng):
    _, _, cfg, idx = _index(rng, n=100)
    q = jnp.zeros((1, 2), jnp.float32)
    with pytest.raises(ValueError, match="candidate pipeline"):
        batched.search(idx, cfg, q, 3, pipeline="telepathy")


def test_candidate_pipeline_replacement_takes_effect(rng):
    """register_candidate_pipeline's 'or replace' contract must survive the
    jit cache: names are resolved EAGERLY to the (hashable) pipeline object,
    so re-registering retraces instead of serving the stale select."""
    _, _, cfg, idx = _index(rng, n=200)
    q = jnp.asarray(rng.normal(size=(2, 2)), jnp.float32)
    base = batched.search(idx, cfg, q, 3, pipeline="fused")  # warm the cache
    orig = batched.get_candidate_pipeline("fused")
    calls = []

    def spy_select(*args, **kw):
        calls.append(1)
        return orig.select(*args, **kw)

    try:
        batched.register_candidate_pipeline(
            batched.CandidatePipeline(name="fused", select=spy_select)
        )
        got = batched.search(idx, cfg, q, 3, pipeline="fused")
        assert calls, "replaced pipeline never ran (stale jit cache)"
        _assert_results_equal(base, got)
    finally:
        batched.register_candidate_pipeline(orig)


def test_unknown_backend_raises(rng):
    _, _, cfg, idx = _index(rng, n=100)
    q = jnp.zeros((1, 2), jnp.float32)
    with pytest.raises(ValueError, match="backend"):
        act.search(idx, cfg, q, 3, backend="tpu-magic")
    with pytest.raises(ValueError, match="backend"):
        act.classify(idx, cfg, q, 3, backend="tpu-magic")
