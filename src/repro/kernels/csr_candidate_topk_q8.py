"""Pallas TPU kernel: int8 CSR candidate scoring -> top-`rerank_k` shortlist.

The exact fused kernel (`csr_candidate_topk.py`) is bandwidth-bound on its
row DMAs: every window row moves `row_cap * d` float32s.  This variant is
the coarse half of the quantized candidate path (`pallas_q8` backend): it
DMAs the candidate rows from the INT8 store (`core/quantized.py`, per-cell
symmetric scales) at a quarter of the bytes, scores them with int32
arithmetic on the VPU, and streams a top-`rerank_k` shortlist of global
CSR row indices.  The caller then exact-re-ranks ONLY those `rerank_k`
rows against the fp32 store (a second, small DMA) with the existing
streaming top-k (`candidate_topk`), so the final (dists, indices) are full
fp32 — see `core/batched.py`.

The int8 store's rows sit in HBM in tiles of Q8_ALIGN rows, and a DMA must
start on a tile boundary.  So each window row DMAs the aligned run of
`q8_window(row_cap)` rows that covers its `row_cap` span, and masks the
slots outside the span.  The per-row scales of every slot are gathered by
XLA before the call into a (B, L, w) block, one column per window row (a
(rows, 1) scale array would be padded to 128 lanes per row in HBM).

Scoring, per window row (one double-buffered int8 window DMA):

  qs   = clip(round(q / s_row), -QCLIP, QCLIP)       int32 (L, d)
  diff = q_points.int32 - qs                          int32
  l2:  acc = sum_chunks f32(sum_chunk diff^2)         int32 inside a chunk
  l1:  acc = sum_chunks   (sum_chunk |diff|)          int32 throughout
  score = s_row * sqrt(acc)   (l2)   |   s_row * acc  (l1)

The query is re-quantized against each row's (= its cell's) scale, so the
integer difference is meaningful per cell; QCLIP bounds the code so a
`<= Q8_MAX_CHUNK`-dim chunk's sum of squares cannot overflow int32 (the
wrapper caps the accumulation chunk accordingly — queries farther than
QCLIP/127 cell-ranges score saturated-far, which only ever demotes
candidates that the exact re-rank would reject anyway).  Scores are
APPROXIMATE by design: the contract is recall (the true top-k lands in the
shortlist), not bit-parity — but masking and tie-breaks (clamped span
starts, row-major window order, first-index argmin) are IDENTICAL to the
exact kernel, so when the shortlist does contain the exact top-k, the
downstream re-rank reproduces `pallas` bit-for-bit
(tests/test_quantized.py).  Tested in interpret mode against
ref.csr_shortlist_q8 (exact match: integer scoring is deterministic), and
compiled for v5e by tests/test_tpu_compile.py.

VMEM per program: 2 * L * d int8 of row buffer (vs 2 * row_cap * d floats
for the fp32 kernel) + the (L, w) window scales.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rank import streaming_topk, tree_sum

# query codes are clipped to +/-QCLIP cell-ranges; with diff bounded by
# QCLIP + 127 a chunk of Q8_MAX_CHUNK dims accumulates |diff|^2 in int32
# with ~3x headroom: 512 * (1023 + 127)^2 < 2^31
QCLIP = 1023
Q8_MAX_CHUNK = 512
# rows per HBM tile of the int8 store: the alignment of every window DMA
Q8_ALIGN = 8


def q8_window(row_cap: int) -> int:
    """L, the rows of one aligned window DMA: covers any row_cap span."""
    return -(-row_cap // Q8_ALIGN) * Q8_ALIGN + Q8_ALIGN


def q8_store_rows(n_pad: int, row_cap: int) -> int:
    """Rows the int8 store must hold for `n_pad` CSR rows: a multiple of
    Q8_ALIGN that fits one aligned window (core/quantized.py pads to it)."""
    return max(-(-n_pad // Q8_ALIGN) * Q8_ALIGN, q8_window(row_cap))


def _window_bases(starts, n_rows: int, row_cap: int):
    """(clamped span starts, aligned window starts), both (B, w) int32.

    The span clamp is the exact kernel's, so the candidate order matches;
    the window start is the tile boundary at or below it, pulled back so the
    whole window stays inside the store.
    """
    s_cl = jnp.clip(starts, 0, max(n_rows - row_cap, 0))
    base = jnp.minimum(
        s_cl // Q8_ALIGN * Q8_ALIGN, n_rows - q8_window(row_cap)
    )
    return s_cl, base


def _kernel(
    span_ref,    # scalar prefetch: (B, 4w) int32 —
                 #   [starts | ends | clamped starts | window bases]
    q_ref,       # (1, d) float32 — this query's ranking vector
    sw_ref,      # (L, w) float32 — scale of every window slot
    store_ref,   # (n_rows, d) int8 — quantized CSR store, stays in HBM/ANY
    outd_ref,    # (1, rerank_k) float32 — approximate scores (+inf pads)
    outi_ref,    # (1, rerank_k) int32 — global CSR row indices (-1 pads)
    buf_ref,     # scratch (2, L, d) int8 — double-buffered window rows
    sem,         # DMA semaphores (2,)
    *,
    w: int,
    row_cap: int,
    rerank_k: int,
    n: int,
    d_chunks: tuple[tuple[int, int], ...],
    metric: str,
):
    i = pl.program_id(0)
    q = q_ref[...]                            # (1, d)
    win = q8_window(row_cap)

    def base(row):
        return pl.multiple_of(span_ref[i, 3 * w + row], Q8_ALIGN)

    def row_dma(slot, row):
        return pltpu.make_async_copy(
            store_ref.at[pl.ds(base(row), win)],
            buf_ref.at[slot],
            sem.at[slot],
        )

    row_dma(0, 0).start()
    # the (L, w) accumulator holds window row `row` in column `row`
    col = jax.lax.broadcasted_iota(jnp.int32, (win, w), 1)
    off = jax.lax.broadcasted_iota(jnp.int32, (win, 1), 0)
    sw = sw_ref[...]

    def body(row, acc):
        dacc, gacc = acc
        slot = jax.lax.rem(row, 2)

        @pl.when(row + 1 < w)
        def _prefetch_next():
            row_dma(jax.lax.rem(row + 1, 2), row + 1).start()

        row_dma(slot, row).wait()
        here = col == row
        # column `row` of the window scales; the other terms are exact zeros
        s = jnp.sum(jnp.where(here, sw, 0.0), axis=1, keepdims=True)
        qs = jnp.clip(
            jnp.round(q / s), -QCLIP, QCLIP
        ).astype(jnp.int32)                   # (L, d)
        diff = buf_ref[slot].astype(jnp.int32) - qs
        if metric == "l1":
            acc = sum(
                tree_sum(jnp.abs(diff[:, c0:c0 + dc])) for c0, dc in d_chunks
            )                                 # int32 (L, 1)
            dist = s * acc.astype(jnp.float32)
        else:
            acc = sum(
                tree_sum(diff[:, c0:c0 + dc] * diff[:, c0:c0 + dc])
                .astype(jnp.float32)          # int32 inside the chunk only
                for c0, dc in d_chunks
            )
            dist = s * jnp.sqrt(acc)
        j = base(row) + off
        lo = span_ref[i, 2 * w + row]
        ok = ((j >= lo) & (j < lo + row_cap) & (j >= span_ref[i, row])
              & (j < span_ref[i, w + row]) & (j < n))
        return (jnp.where(here, jnp.where(ok, dist, jnp.inf), dacc),
                jnp.where(here, j, gacc))

    dacc, gacc = jax.lax.fori_loop(
        0, w, body,
        (jnp.full((win, w), jnp.inf, jnp.float32),
         jnp.zeros((win, w), jnp.int32)),
    )
    # window-row-major, then ascending CSR row: the exact kernel's order
    outd_ref[...], outi_ref[...] = streaming_topk(
        dacc, col * win + off, gacc, rerank_k
    )


def q8_d_chunks(d: int, d_chunk: int | None) -> tuple[tuple[int, int], ...]:
    """The (start, size) accumulation chunks for a d-dim q8 score.

    Unlike the exact kernel (d_chunk=None = ONE reassociation-free sum, for
    bit-parity with the jnp path), the q8 score is approximate by contract,
    so the chunk is always capped at Q8_MAX_CHUNK — the int32 overflow
    bound — and d_chunk only tightens it further.  Shared with the ref
    oracle so kernel and oracle always agree on the summation tree.
    """
    dc = d if d_chunk is None else max(1, min(d_chunk, d))
    dc = min(dc, Q8_MAX_CHUNK)
    return tuple((c0, min(dc, d - c0)) for c0 in range(0, d, dc))


@functools.partial(
    jax.jit,
    static_argnames=(
        "rerank_k", "n", "row_cap", "metric", "d_chunk", "interpret"
    ),
)
def csr_shortlist_q8(
    q_store: jax.Array,     # (n_rows, d) int8 — quantized CSR store
    row_scales: jax.Array,  # (n_rows,) float32 — per-row cell scales
    starts: jax.Array,      # (B, w) int32 — window-row span starts
    ends: jax.Array,        # (B, w) int32 — window-row span ends
    queries: jax.Array,     # (B, d) float32 — per-query ranking vectors
    rerank_k: int,
    n: int,                 # live CSR rows (store rows >= n are padding)
    row_cap: int,
    metric: str = "l2",
    d_chunk: int | None = None,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Contract identical to ref.csr_shortlist_q8.

    Returns (scores (B, rerank_k) float32 approximate, +inf pads; idx
    (B, rerank_k) int32 GLOBAL CSR row indices with -1 pads), best-first.
    """
    n_rows, d = q_store.shape
    b, w = starts.shape
    win = q8_window(row_cap)
    if q_store.dtype != jnp.int8:
        raise ValueError(f"q_store must be int8, got {q_store.dtype}")
    if row_scales.shape != (n_rows,):
        raise ValueError(
            f"row_scales shape {row_scales.shape} != ({n_rows},); one "
            f"scale per store row (core/quantized.py)"
        )
    if n_rows % Q8_ALIGN or n_rows < max(win, n):
        raise ValueError(
            f"int8 store has {n_rows} rows; the kernel needs a multiple of "
            f"{Q8_ALIGN}, at least {max(win, n)} (n={n}, row_cap={row_cap}) "
            f"so every aligned window DMA is in bounds "
            f"(core.quantized.quantize_index pads to q8_store_rows)"
        )
    if ends.shape != (b, w):
        raise ValueError(f"ends shape {ends.shape} != starts {starts.shape}")
    if queries.shape != (b, d):
        raise ValueError(
            f"queries shape {queries.shape} does not match spans batch "
            f"{b} x store dim {d}"
        )
    if not 1 <= rerank_k <= w * row_cap:
        raise ValueError(
            f"rerank_k={rerank_k} must be in [1, window*row_cap = "
            f"{w * row_cap}] (the shortlist is drawn from one window)"
        )
    d_chunks = q8_d_chunks(d, d_chunk)

    s_cl, base = _window_bases(starts.astype(jnp.int32), n_rows, row_cap)
    spans = jnp.concatenate(
        [starts, ends, s_cl, base], axis=1
    ).astype(jnp.int32)
    slots = base[:, None, :] + jnp.arange(win, dtype=jnp.int32)[None, :, None]
    win_scales = jnp.take(row_scales.astype(jnp.float32), slots)  # (B, L, w)
    kernel = functools.partial(
        _kernel,
        w=w, row_cap=row_cap, rerank_k=rerank_k, n=n, d_chunks=d_chunks,
        metric=metric,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            # (B, 1, ·) blocks with the batch dim squeezed, as in the exact
            # kernel: the (8, 128) block rule holds for the two minor dims
            pl.BlockSpec((None, 1, d), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, win, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # int8 store: manual DMA
        ],
        out_specs=[
            pl.BlockSpec((None, 1, rerank_k), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, 1, rerank_k), lambda i, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, win, d), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    outd, outi = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, rerank_k), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, rerank_k), jnp.int32),
        ],
        interpret=interpret,
        name="csr_shortlist_q8",
    )(spans, queries.astype(jnp.float32)[:, None, :], win_scales, q_store)
    return outd[:, 0], outi[:, 0]
