"""The quantized candidate path (core/quantized.py + pallas_q8 backend).

Contract under test, per the candidate-stage design:

  * per-cell symmetric scales match an independent numpy oracle;
  * the int8 shortlist kernel is an EXACT match for its jnp oracle
    (integer scoring is deterministic — no allclose);
  * shortlist containment => bit parity: on every query lane whose int8
    shortlist contains ALL rows the exact fused stage returned, pallas_q8
    reproduces the `pallas` result bit-for-bit (and with a full-window
    rerank_k, on EVERY lane);
  * the store is a pure function of the snapshot, so
    build(P1).insert(P2) == build(P1 u P2) under pallas_q8 and
    mutable.quantized_snapshot equals requantizing a from-scratch rebuild.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as hst

from repro import api
from repro.core import batched
from repro.core.active_search import padded_csr, window_spans
from repro.core.grid import GridConfig, build_index, cell_id_of
from repro.core.projection import identity_projection, to_grid_coords
from repro.core.quantized import quantize_index
from repro.kernels import ops, ref
from repro.utils.quantize import QMAX

CFG = GridConfig(grid_size=64, tile=8, n_classes=3, window=8, row_cap=4,
                 r0=4, k_slack=2.0)
N, B, K = 256, 8, 3


def _build(rng, cfg=CFG, n=N, d=2, spread=1.0):
    pts = jnp.asarray(rng.normal(size=(n, d)) * spread, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)
    idx = build_index(pts, cfg, identity_projection(pts), labels=labels)
    return pts, labels, idx


def _corner_queries(rng, pts, b=B):
    d = pts.shape[1]
    lo = float(jnp.min(pts)) - 0.5
    hi = float(jnp.max(pts)) + 0.5
    corners = np.zeros((4, d), np.float32)
    corners[:, :2] = [[lo, lo], [hi, hi], [lo, hi], [hi, lo]]
    extra = rng.normal(size=(b - 4, d)) * float(jnp.std(pts))
    return jnp.asarray(np.concatenate([corners, extra]), jnp.float32)


# ------------------------------------------------------------------ store ----


def test_per_cell_scales_match_numpy_oracle(rng):
    pts, _labels, idx = _build(rng)
    store = quantize_index(idx, CFG)
    g = CFG.padded_size

    cid = np.asarray(cell_id_of(idx.coords_sorted, g))
    pts_sorted = np.asarray(idx.points_sorted)
    # float32 throughout, mirroring utils.quantize.symmetric_scale — scale
    # agreement must be EXACT or the q8 kernel and oracle drift
    want = np.full((g * g,), 1e-12, np.float32)
    for c in np.unique(cid):
        want[c] = np.maximum(
            np.abs(pts_sorted[cid == c]).max(), np.float32(1e-12)
        )
    want = want / np.float32(QMAX)

    got = np.asarray(store.cell_scales)
    occupied = np.unique(cid)
    # XLA may lower the /127 as a reciprocal multiply (1 ulp off numpy's
    # division); everything downstream uses the jnp value consistently, so
    # ulp-tight is the right bar here — not bit-equal across compilers
    np.testing.assert_allclose(got[occupied], want[occupied], rtol=2e-7)
    # row_scales broadcast the OWNING cell's scale to each CSR row
    row_scales = np.asarray(store.row_scales)[: len(cid), None]
    np.testing.assert_array_equal(row_scales[:, 0], got[cid])
    # codes reconstruct within half a quantization step per dim
    recon = np.asarray(store.q_points[: len(cid)], np.float32) * row_scales
    assert np.all(np.abs(recon - pts_sorted) <= row_scales)


def test_store_is_pure_function_of_index(rng):
    """Bit-identical index -> bit-identical store (the mutability hook)."""
    _pts, _labels, idx = _build(rng)
    a, b = quantize_index(idx, CFG), quantize_index(idx, CFG)
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


# ----------------------------------------------------------------- kernel ----


@pytest.mark.parametrize("metric", ["l2", "l1"])
@pytest.mark.parametrize("d_chunk", [None, 1, 3])
def test_q8_kernel_matches_ref_oracle_exactly(rng, metric, d_chunk):
    cfg = GridConfig(grid_size=64, tile=8, n_classes=3, window=8, row_cap=4,
                     r0=4, k_slack=2.0, metric=metric)
    pts, _labels, idx = _build(rng, cfg=cfg, d=8)
    store = quantize_index(idx, cfg)
    n = int(idx.points_sorted.shape[0])
    q = _corner_queries(rng, pts)
    q_grid = to_grid_coords(idx.proj, q, cfg.grid_size)
    starts, ends = window_spans(idx, cfg, q_grid)
    args = (store.q_points, store.row_scales, starts, ends, q, 6, n,
            cfg.row_cap)
    dk, ik = ops.csr_shortlist_q8(*args, metric=metric, d_chunk=d_chunk)
    dr, ir = ref.csr_shortlist_q8(*args, metric=metric, d_chunk=d_chunk)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    # integer scoring: distances match exactly, not approximately
    ka, kb = np.asarray(dk), np.asarray(dr)
    np.testing.assert_array_equal(np.isinf(ka), np.isinf(kb))
    np.testing.assert_array_equal(ka[np.isfinite(ka)], kb[np.isfinite(kb)])


def test_q8_shortlist_rejects_bad_rerank_k(rng):
    pts, _labels, idx = _build(rng)
    store = quantize_index(idx, CFG)
    q = jnp.asarray(np.zeros((2, 2)), jnp.float32)
    q_grid = to_grid_coords(idx.proj, q, CFG.grid_size)
    starts, ends = window_spans(idx, CFG, q_grid)
    with pytest.raises(ValueError, match="rerank_k"):
        ops.csr_shortlist_q8(store.q_points, store.row_scales, starts, ends,
                             q, CFG.window * CFG.row_cap + 1, N, CFG.row_cap)


# ------------------------------------------- containment => bit parity ------


def _assert_lane_equal(a, b, lanes, msg):
    for field in api.SearchResult._fields:
        ga = np.asarray(getattr(a, field))[lanes]
        gb = np.asarray(getattr(b, field))[lanes]
        np.testing.assert_array_equal(ga, gb, err_msg=f"{msg}:{field}")


@pytest.mark.parametrize("metric", ["l2", "l1"])
@settings(max_examples=6, deadline=None)
@given(
    seed=hst.integers(0, 2**31 - 1),
    spread=hst.sampled_from([0.02, 0.3, 1.5]),
    d_chunk=hst.sampled_from([None, 3]),
)
def test_shortlist_containment_implies_bit_parity(metric, seed, spread,
                                                  d_chunk):
    """Grid corners + skewed densities, both metrics, chunked and not:
    wherever the int8 shortlist contains the exact top-k, the re-ranked
    pallas_q8 result is BIT-IDENTICAL to the exact `pallas` backend — and
    a full-window shortlist makes that every lane."""
    cfg = GridConfig(grid_size=64, tile=8, n_classes=3, window=8, row_cap=4,
                     r0=4, k_slack=2.0, metric=metric)
    rng = np.random.default_rng(seed)
    pts, _labels, idx = _build(rng, cfg=cfg, spread=spread)
    s = api.ActiveSearcher.from_index(idx, cfg)
    q = _corner_queries(rng, pts)

    exact_fused = s.with_plan(backend="pallas", d_chunk=d_chunk).search(q, K)

    # full-window shortlist: containment holds trivially on every lane
    full = s.with_plan(backend="pallas_q8", d_chunk=d_chunk,
                       rerank_k=cfg.window * cfg.row_cap).search(q, K)
    _assert_lane_equal(exact_fused, full, np.arange(B), "full-window")

    # default shortlist: identify covered lanes via the coarse stage and
    # require bit parity exactly there
    store = quantize_index(idx, cfg)
    rk = batched.resolve_rerank_k(cfg, K, None)
    _sld, sl = batched.q8_shortlist(idx, store, cfg, q, rk, d_chunk=d_chunk)
    ids_sorted = padded_csr(idx, cfg.row_cap)[3]
    sl_ids = np.where(np.asarray(sl) >= 0,
                      np.asarray(ids_sorted)[np.maximum(np.asarray(sl), 0)],
                      -2)
    want_ids = np.asarray(exact_fused.ids)
    covered = np.all(
        (want_ids[:, :, None] == sl_ids[:, None, :]).any(-1)
        | ~np.asarray(exact_fused.valid),
        axis=-1,
    )
    got = s.with_plan(backend="pallas_q8", d_chunk=d_chunk).search(q, K)
    _assert_lane_equal(exact_fused, got, np.nonzero(covered)[0], "covered")


# --------------------------------------------------------------- mutation ----


def test_insert_invariance_under_pallas_q8(rng):
    """build(P1).insert(P2) == build(P1 u P2) on the quantized backend."""
    pts = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=400), jnp.int32)
    proj = identity_projection(pts)
    plan = api.ExecutionPlan(backend="pallas_q8")
    grown = api.ActiveSearcher.from_index(
        build_index(pts[:300], CFG, proj, labels=labels[:300]), CFG, plan
    ).insert(pts[300:], labels=labels[300:])
    rebuilt = api.ActiveSearcher.from_index(
        build_index(pts, CFG, proj, labels=labels), CFG, plan
    )
    q = jnp.asarray(rng.normal(size=(B, 2)), jnp.float32)
    a, b = grown.search(q, K), rebuilt.search(q, K)
    _assert_lane_equal(a, b, np.arange(B), "insert-invariance")
    np.testing.assert_array_equal(
        np.asarray(grown.classify(q, K)), np.asarray(rebuilt.classify(q, K))
    )


def test_quantized_snapshot_equals_requantized_rebuild(rng):
    """mutable.quantized_snapshot: the store derived after insert is
    bit-identical to quantizing a from-scratch rebuild (the invariant that
    makes the engine's per-handle memo safe)."""
    from repro.core import mutable as mut

    pts = jnp.asarray(rng.normal(size=(400, 2)), jnp.float32)
    proj = identity_projection(pts)
    state = mut.from_index(build_index(pts[:300], CFG, proj), CFG)
    state = mut.insert(state, CFG, pts[300:])
    index, store = mut.quantized_snapshot(state, CFG)
    rebuilt = build_index(pts, CFG, proj)
    want = quantize_index(rebuilt, CFG)
    np.testing.assert_array_equal(np.asarray(index.points_sorted),
                                  np.asarray(rebuilt.points_sorted))
    for fa, fb in zip(store, want):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


# ---------------------------------------------------------------- backend ----


def test_pallas_q8_backend_smoke(rng):
    """search/classify/count_at all work through the facade; paper mode is
    exact (delegates to the fused stage), and the registered capabilities
    match the design."""
    pts, _labels, idx = _build(rng)
    s = api.ActiveSearcher.from_index(idx, CFG).with_plan(backend="pallas_q8")
    q = _corner_queries(rng, pts)

    res = s.search(q, K)
    assert res.ids.shape == (B, K) and res.dists.dtype == jnp.float32
    assert s.classify(q, K).shape == (B,)
    counts = s.count_at(q, jnp.full((B,), 4, jnp.int32))
    assert counts.shape == (B, CFG.n_classes)

    p = api.ActiveSearcher.from_index(idx, CFG).with_plan(backend="pallas")
    for op in ("search", "classify"):
        a = getattr(s, op)(q, K, mode="paper")
        b = getattr(p, op)(q, K, mode="paper")
        if op == "search":
            _assert_lane_equal(a, b, np.arange(B), "paper")
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    impl = api.get_backend("pallas_q8")
    assert impl.supports_quantized and impl.supports_mutation
    assert impl.supports_interpret and impl.supports_d_chunk
    # chunked streaming is bit-identical, and the store memo survives it
    chunked = s.with_plan(backend="pallas_q8", chunk_size=3).search(q, K)
    _assert_lane_equal(res, chunked, np.arange(B), "chunked")
    assert s.__dict__.get("_quantized_store_cache") is not None
