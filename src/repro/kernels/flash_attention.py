"""Pallas TPU kernel: flash attention (online-softmax), causal or full.

The §Perf hillclimb on `musicgen-medium prefill_32k` showed the memory term
(4.4 s) dominated by (cq, S) score/prob buffers round-tripping HBM — 10 bytes
per score element per layer.  This kernel keeps the running max/denominator/
output accumulator in VMEM scratch across the sequential KV-block axis
(exactly the streaming-top-k pattern brute_knn uses), so HBM traffic drops to
q/k/v/o only.

Grid = (B*H, nq, nk) with the KV axis minormost (sequential on TPU) so the
scratch legally persists across kv steps.  Causal masking is by absolute
block position; fully-masked blocks still run (branchless) — acceptable at
<=2x and TPU-friendly.  MXU alignment: block_q/block_k default 512/512,
hd is the contraction dim.

Validated with interpret=True against ref.flash_attention (= plain softmax
attention) over shape/causal sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,    # (1, bq, hd) float32
    k_ref,    # (1, bk, hd) float32
    v_ref,    # (1, bk, hd) float32
    o_ref,    # (1, bq, hd) float32
    m_ref,    # scratch (bq,) float32 — running max
    l_ref,    # scratch (bq,) float32 — running denominator
    acc_ref,  # scratch (bq, hd) float32 — running numerator
    *,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                  # (bq, hd)
    k = k_ref[0]
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    if causal:
        i = pl.program_id(1)
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # guard: fully-masked rows keep m = -inf; exp(s - (-inf)) must be 0
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(m_new[:, None] == NEG_INF, 0.0, p)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,   # (B, S, H, hd)
    k: jax.Array,   # (B, T, H, hd) — pre-expanded GQA
    v: jax.Array,   # (B, T, H, hd)
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    *,
    interpret: bool,
) -> jax.Array:
    """Contract identical to ref.flash_attention."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    nq = -(-s // bq)
    nk = -(-t // bk)
    if nq * bq != s or nk * bk != t:
        raise ValueError(f"seq {s}/{t} must divide blocks {bq}/{bk}")

    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd).astype(jnp.float32)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h, t, hd).astype(jnp.float32)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h, t, hd).astype(jnp.float32)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, causal=causal, scale=1.0 / (hd ** 0.5)
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, s, hd), 1, 2)
