"""Pallas kernels (interpret=True on CPU) vs the pure-jnp ref.py oracles.
Shape/dtype sweeps per kernel, as the assignment requires."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as hst

from repro.kernels import csr_candidate_topk as csr_mod, ops, ref


# ------------------------------------------------------------ tile_count ----


# CONTRACT: kernel == ref when the circle fits the T-cell window, i.e.
# r <= scale * (tile/2 - 1.5).  pyramid.level_for_radius guarantees this.
def _rmax(tile, scale):
    return scale * (tile / 2 - 1.5)


@pytest.mark.parametrize("s,tile,c", [(32, 8, 1), (64, 16, 3), (128, 16, 4), (64, 8, 8)])
@pytest.mark.parametrize("scale", [1, 2, 4])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_tile_count_sweep(rng, s, tile, c, scale, metric):
    level = jnp.asarray(rng.integers(0, 5, size=(s, s, c)), jnp.int32)
    b = 9
    q = jnp.asarray(rng.uniform(0, s * scale, size=(b, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(0.5, _rmax(tile, scale), size=(b,)), jnp.float32)
    got = ops.tile_count(level, q, r, scale, tile, metric=metric, interpret=True)
    want = ref.tile_count(level, q, r, scale, tile, metric=metric)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tile_count_edges(rng):
    """Queries at corners/borders where the window clamps."""
    s, tile = 32, 8
    level = jnp.asarray(rng.integers(0, 3, size=(s, s, 2)), jnp.int32)
    q = jnp.asarray([[0.0, 0.0], [31.9, 31.9], [0.0, 31.9], [16.0, 0.0]], jnp.float32)
    r = jnp.asarray([2.0, 2.5, 1.5, 2.4], jnp.float32)
    got = ops.tile_count(level, q, r, 1, tile, interpret=True)
    want = ref.tile_count(level, q, r, 1, tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tile_count_beyond_window_matches_ref():
    """Past the contract radius the kernel masks to the clamped T-window, so
    it stays bit-identical to ref (which truncates at its T-window) instead
    of overcounting from its 2Tx2T block cover."""
    rng = np.random.default_rng(1)
    s, tile = 32, 8
    level = jnp.asarray(rng.integers(0, 3, size=(s, s, 1)), jnp.int32)
    q = jnp.asarray(rng.uniform(0, s, size=(6, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(4.0, 7.5, size=(6,)), jnp.float32)
    got = np.asarray(ops.tile_count(level, q, r, 1, tile, interpret=True))
    want = np.asarray(ref.tile_count(level, q, r, 1, tile))
    np.testing.assert_array_equal(got, want)


def test_tile_count_window_parity_grid_edge():
    """The headline regime for the window-parity fix: queries at grid
    corners/borders with radii far past the contract, where the clamped
    window and the circle disagree the most."""
    rng = np.random.default_rng(2)
    s, tile = 32, 8
    level = jnp.asarray(rng.integers(0, 4, size=(s, s, 2)), jnp.int32)
    q = jnp.asarray(
        [[0.0, 0.0], [31.9, 31.9], [0.0, 31.9], [31.9, 0.0], [0.5, 16.0]],
        jnp.float32,
    )
    r = jnp.asarray([10.0, 20.0, 31.0, 8.0, 15.0], jnp.float32)
    got = np.asarray(ops.tile_count(level, q, r, 1, tile, interpret=True))
    want = np.asarray(ref.tile_count(level, q, r, 1, tile))
    np.testing.assert_array_equal(got, want)


@settings(max_examples=20, deadline=None)
@given(seed=hst.integers(0, 2**31 - 1))
def test_tile_count_property(seed):
    rng = np.random.default_rng(seed)
    s = int(rng.choice([16, 32, 64]))
    tile = int(rng.choice([8, 16]))
    tile = min(tile, s)
    c = int(rng.integers(1, 5))
    scale = int(rng.choice([1, 2]))
    level = jnp.asarray(rng.integers(0, 4, size=(s, s, c)), jnp.int32)
    b = int(rng.integers(1, 6))
    q = jnp.asarray(rng.uniform(0, s * scale, size=(b, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(0.5, _rmax(tile, scale), size=(b,)), jnp.float32)
    got = ops.tile_count(level, q, r, scale, tile, interpret=True)
    want = ref.tile_count(level, q, r, scale, tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------- tile_count_multilevel ----


def _pyramid_fixture(rng, grid=64, tile=8, c=2):
    from repro.core.grid import GridConfig, build_index
    from repro.core.projection import identity_projection

    pts = jnp.asarray(rng.normal(size=(800, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, c, size=800), jnp.int32)
    cfg = GridConfig(grid_size=grid, tile=tile, n_classes=c, r0=8)
    idx = build_index(pts, cfg, identity_projection(pts), labels=labels)
    return cfg, idx


@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_tile_count_multilevel_matches_ref(rng, metric):
    """One level-scheduled pallas_call == the stacked per-level select, for
    radii spanning every pyramid level."""
    from repro.core import pyramid as pyr

    cfg, idx = _pyramid_fixture(rng)
    b = 16
    q = jnp.asarray(rng.uniform(0, cfg.padded_size, size=(b, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(0.5, cfg.max_radius, size=(b,)), jnp.float32)
    lv = pyr.level_for_radius(r, cfg)
    got = ops.tile_count_multilevel(
        idx.pyr_tiles, q, r, lv, cfg.tile, cfg.level_nblks, metric=metric,
        interpret=True,
    )
    want = ref.tile_count_multilevel(idx.pyramid, q, r, lv, cfg.tile, metric=metric)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tile_count_multilevel_forced_levels(rng):
    """Level is an INPUT, not derived: forcing every query to each level in
    turn must reproduce that level's single-level kernel — including levels
    whose window the circle overruns (window parity)."""
    cfg, idx = _pyramid_fixture(rng)
    b = 8
    q = jnp.asarray(rng.uniform(0, cfg.padded_size, size=(b, 2)), jnp.float32)
    r = jnp.asarray(rng.uniform(0.5, cfg.max_radius / 2, size=(b,)), jnp.float32)
    for lv in range(cfg.levels):
        levels = jnp.full((b,), lv, jnp.int32)
        got = ops.tile_count_multilevel(
            idx.pyr_tiles, q, r, levels, cfg.tile, cfg.level_nblks,
            interpret=True,
        )
        want = ref.tile_count(idx.pyramid[lv], q, r, 1 << lv, cfg.tile)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want), err_msg=f"level {lv}"
        )


def test_tile_count_multilevel_max_radius_top_level(rng):
    """r == max_radius clamps level selection at levels-1; the top tile IS
    the whole level there, so the count must equal the total mass inside the
    circle of the full grid."""
    from repro.core import pyramid as pyr

    cfg, idx = _pyramid_fixture(rng)
    b = 5
    q = jnp.asarray(rng.uniform(0, cfg.padded_size, size=(b, 2)), jnp.float32)
    r = jnp.full((b,), float(cfg.max_radius), jnp.float32)
    lv = pyr.level_for_radius(r, cfg)
    assert int(lv[0]) == cfg.levels - 1
    got = ops.tile_count_multilevel(
        idx.pyr_tiles, q, r, lv, cfg.tile, cfg.level_nblks, interpret=True
    )
    want = ref.tile_count_multilevel(idx.pyramid, q, r, lv, cfg.tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tile_count_multilevel_bad_layout_raises(rng):
    cfg, idx = _pyramid_fixture(rng)
    with pytest.raises(ValueError, match="tiles shape"):
        ops.tile_count_multilevel(
            idx.pyr_tiles[:-1], jnp.zeros((1, 2), jnp.float32),
            jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            cfg.tile, cfg.level_nblks, interpret=True,
        )


# -------------------------------------------------------- candidate_topk ----


@pytest.mark.parametrize("b,c,d,k", [(4, 16, 8, 3), (2, 64, 32, 11), (1, 128, 300, 16), (8, 32, 512, 5)])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_candidate_topk_sweep(rng, b, c, d, k, metric):
    cand = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=(b, c)) > 0.3)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    gd, gi = ops.candidate_topk(cand, valid, q, k, metric=metric, d_chunk=128,
                                interpret=True)
    wd, wi = ref.candidate_topk(cand, valid, q, k, metric=metric)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-5, atol=1e-5)
    # indices may differ on exact ties; check distances of chosen candidates
    for i in range(b):
        for j in range(k):
            if wi[i, j] >= 0:
                assert gi[i, j] >= 0


def test_candidate_topk_all_invalid(rng):
    cand = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32)
    valid = jnp.zeros((2, 8), bool)
    q = jnp.zeros((2, 4), jnp.float32)
    gd, gi = ops.candidate_topk(cand, valid, q, 3, interpret=True)
    assert bool(jnp.all(jnp.isinf(gd)))
    assert bool(jnp.all(gi == -1))


def test_candidate_topk_c_smaller_than_k(rng):
    """k exceeds the candidate count: the first C slots match the k=C oracle,
    the rest pad with +inf / -1 (the batched backend relies on this)."""
    b, c, d, k = 3, 5, 6, 9
    cand = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
    valid = jnp.ones((b, c), bool)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    gd, gi = ops.candidate_topk(cand, valid, q, k, interpret=True)
    wd, wi = ref.candidate_topk(cand, valid, q, c)  # oracle at k=C
    np.testing.assert_allclose(np.asarray(gd[:, :c]), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi[:, :c]), np.asarray(wi))
    assert bool(jnp.all(jnp.isinf(gd[:, c:])))
    assert bool(jnp.all(gi[:, c:] == -1))


def test_candidate_topk_partially_invalid_fewer_than_k(rng):
    """Fewer VALID candidates than k: invalid slots never leak into the top-k."""
    b, c, d, k = 2, 16, 4, 8
    cand = jnp.asarray(rng.normal(size=(b, c, d)), jnp.float32)
    valid = jnp.zeros((b, c), bool).at[:, :3].set(True)  # only 3 valid
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    gd, gi = ops.candidate_topk(cand, valid, q, k, interpret=True)
    wd, wi = ref.candidate_topk(cand, valid, q, k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    assert bool(jnp.all(gi[:, 3:] == -1))


# ---------------------------------------------------- csr_candidate_topk ----


def _csr_fixture(rng, n=600, d=6, b=5, w=7, rcap=16):
    store = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, n - 4, size=(b, w)), jnp.int32)
    # spans from empty through overflowing (end - start > rcap)
    ends = starts + jnp.asarray(
        rng.integers(0, rcap + 6, size=(b, w)), jnp.int32
    )
    ends = jnp.minimum(ends, n)
    return store, starts, ends, q


def _csr_sweep_cases():
    """metric x k x (b, w, slab_rows); the first shape keeps the ids
    "<k>-<metric>" it had before the other shapes joined."""
    shapes = [(5, 7, None),  # whole windows in flight, programs crossed
              (1, 7, None),  # one program: nothing to start ahead
              (2, 7, None),  # the online cell's padded batch
              (4, 9, 2),     # a ring: slabs of 2 rows, the last group of 1
              (3, 8, 4)]     # a ring of two equal groups per query
    for b, w, slab_rows in shapes:
        tag = "" if (b, w) == (5, 7) else f"-b{b}-w{w}"
        tag += f"-slab{slab_rows}" if slab_rows else ""
        for k in (1, 5, 16):
            for metric in ("l2", "l1"):
                yield pytest.param(metric, k, b, w, slab_rows,
                                   id=f"{k}-{metric}{tag}")


@pytest.mark.parametrize("metric,k,b,w,slab_rows", _csr_sweep_cases())
def test_csr_candidate_topk_sweep(rng, monkeypatch, metric, k, b, w,
                                  slab_rows):
    """The fused gather+distance+top-k kernel == its dense-gather oracle
    BIT-FOR-BIT (global CSR indices included), spans spanning empty rows,
    partial rows, and row_cap-overflowing rows, drawn per query.  A
    `slab_rows` case shrinks the slab budget until `rows_in_flight` gives
    fewer rows than the window, so the rows go through groups of that many
    (each case's shapes are its own, so the jit traces it afresh)."""
    store, starts, ends, q = _csr_fixture(rng, b=b, w=w)
    rcap = 16
    if slab_rows is not None:
        budget = 2 * slab_rows * rcap * 128 * 4  # (8, 128)-padded rows
        depths, rows_in_flight = [], csr_mod.rows_in_flight

        def shrunk(w_, row_cap, d):
            depths.append(rows_in_flight(w_, row_cap, d, budget))
            return depths[-1]

        monkeypatch.setattr(csr_mod, "rows_in_flight", shrunk)
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, k, store.shape[0], rcap, metric=metric,
        interpret=True,
    )
    if slab_rows is not None:
        assert depths == [slab_rows] and slab_rows < w
    wd, wi = ref.csr_candidate_topk(
        store, starts, ends, q, k, store.shape[0], rcap, metric=metric
    )
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_csr_candidate_topk_paper_mode():
    """center_cells + radii reproduce mode='paper': rank floor(coords)+0.5
    cell centers and mask candidates outside the Eq.-1 circle.

    Local generator, not the session rng: a cell center can land within
    1 ulp of the circle radius, where the kernel's and the oracle's
    inclusion masks may flip independently — the drawn geometry must not
    depend on how many tests consumed the session stream before this one."""
    local = np.random.default_rng(7)
    store, starts, ends, _ = _csr_fixture(local, d=2)
    store = store * 8.0  # spread across cells so floor() matters
    q = jnp.asarray(local.uniform(-16, 16, size=(5, 2)), jnp.float32)
    radii = jnp.asarray(local.uniform(1.0, 12.0, size=(5,)), jnp.float32)
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, 4, store.shape[0], 16, radii=radii,
        center_cells=True, interpret=True,
    )
    wd, wi = ref.csr_candidate_topk(
        store, starts, ends, q, 4, store.shape[0], 16, radii=radii,
        center_cells=True,
    )
    # distances allclose / indices exact, like the drawn-d sweep: the two
    # reductions can sit 1 ulp apart (the pinned inter-kernel caveat)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_csr_candidate_topk_live_boundary(rng):
    """Spans that reach past the live CSR length n (store rows >= n are
    padding) never surface a padded row."""
    n_live, n_pad = 40, 64
    store = jnp.asarray(rng.normal(size=(n_pad, 4)), jnp.float32)
    starts = jnp.asarray([[30, 38, 0]], jnp.int32)
    ends = jnp.asarray([[50, 64, 8]], jnp.int32)  # overrun the live region
    q = jnp.zeros((1, 4), jnp.float32)
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, 32, n_live, 16, interpret=True
    )
    wd, wi = ref.csr_candidate_topk(store, starts, ends, q, 32, n_live, 16)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    live = np.asarray(gi)[np.asarray(gi) >= 0]
    assert (live < n_live).all()


def test_csr_candidate_topk_k_exceeds_window(rng):
    """k > w*row_cap: the streaming select pads with +inf / -1."""
    store, starts, ends, q = _csr_fixture(rng, b=2, w=2, rcap=4)
    k = 2 * 4 + 3
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, k, store.shape[0], 4, interpret=True
    )
    wd, wi = ref.csr_candidate_topk(store, starts, ends, q, k,
                                    store.shape[0], 4)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(wd))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
    assert bool(jnp.all(jnp.isinf(gd[:, -3:]))) and bool(jnp.all(gi[:, -3:] == -1))


def test_csr_candidate_topk_d_chunk_accumulation(rng):
    """An explicit d_chunk cap trades the single-sum reduction for bounded
    VMEM (documented reassociation of the float32 sums): distances stay
    allclose to the one-step oracle and the selected candidates agree."""
    store, starts, ends, q = _csr_fixture(rng, d=10)
    n, rcap, k = store.shape[0], 16, 5
    wd, wi = ref.csr_candidate_topk(store, starts, ends, q, k, n, rcap)
    for dc in (3, 4, 10, 64):
        gd, gi = ops.csr_candidate_topk(
            store, starts, ends, q, k, n, rcap, d_chunk=dc, interpret=True
        )
        np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"d_chunk={dc}")
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi),
                                      err_msg=f"d_chunk={dc}")


def test_csr_candidate_topk_matches_dense_kernel_large_d(rng):
    """The inter-KERNEL invariant behind backend parity: fused == the
    gather pipeline's dense candidate_topk BIT-for-bit — including d large
    enough (d=10 here) that both kernels' reductions drift 1 ulp from the
    big-tensor jnp oracle in the same direction."""
    store, starts, ends, q = _csr_fixture(rng, d=10)
    n, rcap, k = store.shape[0], 16, 5
    b = q.shape[0]
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, k, n, rcap, interpret=True
    )
    s_cl = jnp.clip(starts, 0, n - rcap)
    j = s_cl[:, :, None] + jnp.arange(rcap, dtype=jnp.int32)
    ok = (j >= starts[:, :, None]) & (j < ends[:, :, None]) & (j < n)
    flat = j.reshape(b, -1)
    dd, di = ops.candidate_topk(
        jnp.take(store, flat, axis=0), ok.reshape(b, -1), q, k,
        d_chunk=store.shape[1], interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(dd))
    gflat = jnp.where(
        di >= 0, jnp.take_along_axis(flat, jnp.maximum(di, 0), axis=1), -1
    )
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(gflat))


@settings(max_examples=10, deadline=None)
@given(seed=hst.integers(0, 2**31 - 1))
def test_csr_candidate_topk_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(32, 400))
    d = int(rng.integers(2, 12))
    b = int(rng.integers(1, 5))
    w = int(rng.integers(1, 6))
    rcap = int(rng.choice([4, 8, 16]))
    rcap = min(rcap, n)
    k = int(rng.integers(1, 9))
    store = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    starts = jnp.asarray(rng.integers(0, n, size=(b, w)), jnp.int32)
    ends = jnp.minimum(
        starts + jnp.asarray(rng.integers(0, rcap + 4, size=(b, w)), jnp.int32),
        n,
    )
    gd, gi = ops.csr_candidate_topk(
        store, starts, ends, q, k, n, rcap, interpret=True
    )
    wd, wi = ref.csr_candidate_topk(store, starts, ends, q, k, n, rcap)
    # at larger drawn d the kernel's per-row reduction can sit 1 ulp from
    # the big-tensor oracle (see ..._matches_dense_kernel_large_d, which
    # pins the inter-kernel BIT contract); selection must still agree
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_csr_candidate_topk_store_too_small_raises(rng):
    store = jnp.zeros((4, 3), jnp.float32)
    with pytest.raises(ValueError, match="row_cap"):
        ops.csr_candidate_topk(
            store, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, 3), jnp.float32), 2, 4, 8, interpret=True,
        )


def test_tile_count_zero_radius(rng):
    """r=0: only a cell whose center coincides with the query could count."""
    s, tile = 32, 8
    level = jnp.asarray(rng.integers(0, 3, size=(s, s, 2)), jnp.int32)
    q = jnp.asarray([[10.5, 20.5], [3.0, 7.0]], jnp.float32)  # on/off centers
    r = jnp.zeros((2,), jnp.float32)
    got = ops.tile_count(level, q, r, 1, tile, interpret=True)
    want = ref.tile_count(level, q, r, 1, tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tile_count_full_pyramid_levels(rng):
    """Every level of a real pyramid agrees with the oracle at its scale —
    the exact sweep the batched radius loop performs."""
    from repro.core.grid import GridConfig, build_index
    from repro.core.projection import identity_projection

    pts = jnp.asarray(rng.normal(size=(500, 2)), jnp.float32)
    cfg = GridConfig(grid_size=64, tile=8, r0=8)
    idx = build_index(pts, cfg, identity_projection(pts))
    q = jnp.asarray(rng.uniform(0, cfg.padded_size, size=(7, 2)), jnp.float32)
    for lv, arr in enumerate(idx.pyramid):
        scale = 1 << lv
        r = jnp.asarray(
            rng.uniform(0.5, scale * (cfg.tile / 2 - 1.5), size=(7,)), jnp.float32
        )
        got = ops.tile_count(arr, q, r, scale, cfg.tile, interpret=True)
        want = ref.tile_count(arr, q, r, scale, cfg.tile)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"level {lv}")


# ------------------------------------------------------------- brute_knn ----


@pytest.mark.parametrize("b,n,d,k", [(4, 100, 8, 5), (2, 1000, 16, 11), (128, 700, 4, 3), (1, 64, 128, 20)])
def test_brute_knn_sweep(rng, b, n, d, k):
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    gd, gi = ops.brute_knn(q, x, k, block_q=32, block_n=128, interpret=True)
    wd, wi = ref.brute_knn(q, x, k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-4, atol=1e-4)
    # id sets agree except for ties at the k-th distance
    for i in range(b):
        inter = set(np.asarray(gi[i]).tolist()) & set(np.asarray(wi[i]).tolist())
        assert len(inter) >= k - 2


def test_brute_knn_k_bigger_than_blocks(rng):
    q = jnp.asarray(rng.normal(size=(3, 6)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(50, 6)), jnp.float32)
    gd, gi = ops.brute_knn(q, x, 7, block_q=2, block_n=16, interpret=True)
    wd, _ = ref.brute_knn(q, x, 7)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=hst.integers(0, 2**31 - 1))
def test_brute_knn_property(seed):
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 9))
    n = int(rng.integers(5, 300))
    d = int(rng.integers(2, 40))
    k = int(rng.integers(1, min(n, 12) + 1))
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    gd, _ = ops.brute_knn(q, x, k, block_q=16, block_n=64, interpret=True)
    wd, _ = ref.brute_knn(q, x, k)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(wd), rtol=1e-4, atol=1e-4)


# -------------------------------------------------------- flash_attention ----


@pytest.mark.parametrize("b,s,t,h,hd,causal", [
    (2, 64, 64, 4, 32, True),
    (1, 128, 128, 2, 64, True),
    (2, 32, 96, 3, 16, False),
    (1, 256, 256, 1, 128, True),
])
def test_flash_attention_sweep(rng, b, s, t, h, hd, causal):
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(rng):
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 32)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@settings(max_examples=10, deadline=None)
@given(seed=hst.integers(0, 2**31 - 1))
def test_flash_attention_property(seed):
    rng = np.random.default_rng(seed)
    bq = int(rng.choice([8, 16, 32]))
    nq = int(rng.integers(1, 5))
    nk = int(rng.integers(1, 5))
    h = int(rng.integers(1, 4))
    hd = int(rng.choice([16, 32, 64]))
    causal = bool(rng.integers(0, 2)) and nq == nk
    s, t = bq * nq, bq * nk
    q = jnp.asarray(rng.normal(size=(1, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, h, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bq,
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
