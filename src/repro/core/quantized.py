"""QuantizedStore: the CSR candidate store at int8 width (pallas_q8).

The fused candidate kernel is bandwidth-bound on its row DMAs — every
window row moves `row_cap * d` float32s from HBM per query.  This module
holds the SAME CSR-sorted points at 1 byte/dim with per-cell symmetric
scales (`repro.utils.quantize`, the codec shared with the gradient
compressor):

  cell_scales[c] = max(|x|) over points of cell c / 127     (eps-floored)
  q_points[j]    = clip(round(points_sorted[j] / scale_of_cell(j)))

Per-CELL scales — not per-tensor — because a cell is the locality unit of
active search: points that share a bucket are close in the projected plane
and typically similar in magnitude, so the codebook adapts to local range
instead of paying the global max everywhere.  `row_scales` broadcasts the
owning cell's scale to every CSR row (including the slack rows, which
quantize to zeros under the eps floor).  The store carries `n_q` rows: the
`padded_csr` rows, padded up to the int8 kernel's DMA alignment
(`kernels.csr_candidate_topk_q8.q8_store_rows`); row indices are the fp32
store's, so span arithmetic stays identical.

The store is DERIVED: `quantize_index` is a pure function of a
`GridIndex`, and `mutable.snapshot` reproduces `build_index`'s CSR order
bit-for-bit, so requantizing after insert/delete yields the exact store a
from-scratch rebuild would (the mutability invariant extends to the
quantized path for free — `mutable.quantized_snapshot` packages that, and
tests/test_quantized.py pins it).  The engine memoizes the store per
handle (`core/engine.py`), and every mutation returns a new handle, so the
memo can never serve a stale store.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.active_search import padded_csr
from repro.core.grid import GridConfig, GridIndex, cell_id_of
from repro.kernels.csr_candidate_topk_q8 import q8_store_rows
from repro.utils.quantize import quantize_with_scale, symmetric_scale


class QuantizedStore(NamedTuple):
    """int8 view of the padded CSR point store (same row order/indices)."""

    q_points: jax.Array    # (n_q, d) int8 — CSR-sorted points, quantized
    row_scales: jax.Array  # (n_q,) float32 — owning cell's scale per row
    cell_scales: jax.Array  # (padded_size**2,) float32 — per-cell scale


@partial(jax.jit, static_argnames=("cfg",))
def quantize_index(index: GridIndex, cfg: GridConfig) -> QuantizedStore:
    """Per-cell symmetric int8 quantization of the padded CSR store.

    jit-able; the only data dependencies are the CSR arrays, so the result
    is a pure function of the snapshot (bit-identical stores for
    bit-identical indexes — the property the mutable path relies on).
    """
    pts, _crd, _lab, _ids, _n, n_pad = padded_csr(index, cfg.row_cap)
    g = cfg.padded_size
    n = index.points_sorted.shape[0]

    cid = cell_id_of(index.coords_sorted, g)                      # (n,)
    point_max = jnp.max(jnp.abs(index.points_sorted), axis=1)     # (n,)
    cell_max = jax.ops.segment_max(
        point_max, cid, num_segments=g * g, indices_are_sorted=True
    )
    # empty cells come back -inf; floor them so the scale stays finite
    cell_scales = symmetric_scale(jnp.maximum(cell_max, 0.0))     # (g*g,)

    # slack rows (padded_csr's, then the kernel's DMA alignment): eps
    # scale, zero codes
    n_q = q8_store_rows(n_pad, cfg.row_cap)
    row_scales = jnp.concatenate(
        [cell_scales[cid], jnp.full((n_q - n,), symmetric_scale(0.0))]
    ).astype(jnp.float32)                                         # (n_q,)
    pts = jnp.pad(pts, ((0, n_q - n_pad), (0, 0)))
    return QuantizedStore(
        q_points=quantize_with_scale(pts, row_scales[:, None]),
        row_scales=row_scales,
        cell_scales=cell_scales,
    )
