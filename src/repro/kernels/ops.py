"""Public jit'd wrappers for the Pallas kernels.

`interpret=None` (the default everywhere) resolves in `resolve_interpret`:
the Pallas interpreter on the CPU backend, Mosaic-compiled kernels on a TPU.
An explicit `interpret=True` still runs the interpreter (tests use it); the
served path refuses it on a TPU (`core/engine.py`).
"""

from __future__ import annotations

import jax

from repro.kernels.brute_knn import brute_knn as _brute_knn
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.candidate_topk import candidate_topk as _candidate_topk
from repro.kernels.csr_candidate_topk import (
    csr_candidate_topk as _csr_candidate_topk,
)
from repro.kernels.csr_candidate_topk_q8 import (
    csr_shortlist_q8 as _csr_shortlist_q8,
)
from repro.kernels.tile_count import tile_count as _tile_count
from repro.kernels.tile_count_multilevel import (
    tile_count_multilevel as _tile_count_multilevel,
)


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place `interpret=None` is decided: interpret on the CPU
    backend (Mosaic needs a TPU), compile to Mosaic on any other."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def tile_count(level_arr, queries, radii, scale, tile, metric="l2", interpret=None):
    return _tile_count(
        level_arr, queries, radii, scale, tile, metric=metric,
        interpret=resolve_interpret(interpret),
    )


def tile_count_multilevel(
    tiles, queries, radii, levels, tile, nblks, metric="l2", interpret=None,
    active=None,
):
    return _tile_count_multilevel(
        tiles, queries, radii, levels, tile, nblks, metric=metric,
        interpret=resolve_interpret(interpret), active=active,
    )


def candidate_topk(candidates, valid, queries, k, metric="l2", d_chunk=512, interpret=None):
    return _candidate_topk(
        candidates, valid, queries, k, metric=metric, d_chunk=d_chunk,
        interpret=resolve_interpret(interpret),
    )


def csr_candidate_topk(
    store, starts, ends, queries, k, n, row_cap, metric="l2", radii=None,
    center_cells=False, d_chunk=None, interpret=None,
):
    return _csr_candidate_topk(
        store, starts, ends, queries, k, n, row_cap, metric=metric,
        radii=radii, center_cells=center_cells, d_chunk=d_chunk,
        interpret=resolve_interpret(interpret),
    )


def csr_shortlist_q8(
    q_store, row_scales, starts, ends, queries, rerank_k, n, row_cap,
    metric="l2", d_chunk=None, interpret=None,
):
    return _csr_shortlist_q8(
        q_store, row_scales, starts, ends, queries, rerank_k, n, row_cap,
        metric=metric, d_chunk=d_chunk, interpret=resolve_interpret(interpret),
    )


def brute_knn(queries, points, k, block_q=128, block_n=512, interpret=None):
    return _brute_knn(
        queries, points, k, block_q=block_q, block_n=block_n,
        interpret=resolve_interpret(interpret),
    )


def flash_attention(q, k, v, causal=True, block_q=512, block_k=512, interpret=None):
    return _flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=resolve_interpret(interpret),
    )
