"""The candidate-bytes arithmetic (`reference.window_bytes`) against a plain
count of the CSR window spans on a tiny index."""

from __future__ import annotations

import numpy as np

import reference


def test_window_bytes_matches_span_count():
    rng = np.random.default_rng(0)
    grid = {"grid_size": 64, "tile": 16, "window": 8, "row_cap": 4,
            "r0": 4, "max_iters": 16, "k_slack": 1.0}
    n, d = 3000, 24
    # clustered coordinates, so that some window rows overflow row_cap
    coords = np.clip(np.concatenate([
        rng.normal(32, 3, (n // 2, 2)), rng.uniform(0, 64, (n // 2, 2))]),
        0, 63.99)
    g = reference.Grid(coords, grid)
    qc = rng.uniform(0, 64, (50, 2))
    got = reference.window_bytes(g, qc, d)
    cells = np.floor(coords).astype(int)
    for b in range(qc.shape[0]):
        cx, cy = int(qc[b, 0]), int(qc[b, 1])
        x0 = min(max(cx - 4, 0), 64 - 8)
        y0 = min(max(cy - 4, 0), 64 - 8)
        want = 0
        for x in range(x0, x0 + 8):
            in_row = np.sum((cells[:, 0] == x) & (cells[:, 1] >= y0)
                            & (cells[:, 1] < y0 + 8))
            want += min(int(in_row), grid["row_cap"])
        assert got[b] == want * d * 4
