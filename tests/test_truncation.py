"""Property test for the SearchResult.truncated contract (window_spans /
row_cap truncation).

`truncated` must be raised EXACTLY when candidates were dropped before the
re-rank, i.e. when

  (a) the final Eq.-1 circle exceeds the candidate window
      (2 r + 1 > cfg.window), or
  (b) any window row's CSR span holds more than row_cap points (the gather
      keeps only the first row_cap records of each row).

The expectation is recomputed here in pure numpy straight from the CSR
offsets — an oracle independent of `active_search.window_spans` — and
checked on the jnp reference and the pallas path (fused
csr_candidate_topk), for clustered data (row
overflow without window overrun), spread data (neither), and grid-corner
queries (clamped windows on both axes).
"""

import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, strategies as hst

from repro import api
from repro.core import active_search as act
from repro.core.grid import GridConfig, build_index
from repro.core.projection import identity_projection

CFG = GridConfig(grid_size=64, tile=8, window=8, row_cap=4, r0=4,
                 k_slack=2.0)
N, B, K = 256, 8, 3


def _expected_row_overflow(index, cfg, q_grid) -> np.ndarray:
    """any(end - start > row_cap) per query, straight from the offsets."""
    g, w = cfg.padded_size, cfg.window
    offs = np.asarray(index.offsets)
    qg = np.asarray(q_grid)
    cx = np.floor(qg[:, 0]).astype(np.int64)
    cy = np.floor(qg[:, 1]).astype(np.int64)
    x0 = np.clip(cx - w // 2, 0, g - w)
    y0 = np.clip(cy - w // 2, 0, g - w)
    rows = x0[:, None] + np.arange(w)                    # (B, w)
    start = offs[rows * g + y0[:, None]]
    end = offs[rows * g + (y0[:, None] + w)]
    return (end - start > cfg.row_cap).any(axis=1)


@settings(max_examples=8, deadline=None)
@given(
    seed=hst.integers(0, 2**31 - 1),
    spread=hst.sampled_from([0.02, 0.3, 1.5]),
)
def test_truncated_iff_window_overrun_or_row_overflow(seed, spread):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.normal(size=(N, 2)) * spread, jnp.float32)
    idx = build_index(pts, CFG, identity_projection(pts))
    s = api.ActiveSearcher.from_index(idx, CFG)

    lo = float(jnp.min(pts)) - 0.5
    hi = float(jnp.max(pts)) + 0.5
    corners = np.asarray([[lo, lo], [hi, hi], [lo, hi], [hi, lo]])
    q = jnp.asarray(
        np.concatenate([corners, rng.normal(size=(B - 4, 2)) * spread]),
        jnp.float32,
    )
    from repro.core import projection as proj_lib

    q_grid = proj_lib.to_grid_coords(idx.proj, q, CFG.grid_size)
    overflow = _expected_row_overflow(idx, CFG, q_grid)

    results = {
        name: s.with_plan(backend=name).search(q, K)
        for name in ("jnp", "pallas")
    }
    ref = results["jnp"]
    window_overrun = 2 * np.asarray(ref.radius) + 1 > CFG.window
    expected = window_overrun | overflow
    for name, res in results.items():
        np.testing.assert_array_equal(
            np.asarray(res.truncated), expected, err_msg=name
        )
        np.testing.assert_array_equal(
            np.asarray(res.radius), np.asarray(ref.radius), err_msg=name
        )
