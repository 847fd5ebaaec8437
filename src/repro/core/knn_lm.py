"""kNN-LM head: the paper's retrieval primitive as a production LM feature.

Khandelwal-style kNN-LM: a datastore maps hidden states h_t -> next token
y_{t+1}.  At serve time the LM distribution is interpolated with a kNN
distribution obtained by active search over the datastore:

    p(y) = lam * p_knn(y) + (1 - lam) * p_lm(y)
    p_knn(y)  propto  sum_{(h_i, y_i) in topk(h)} 1[y_i = y] * exp(-d(h, h_i) / T)

The datastore rides in GridIndex.labels_sorted (token ids are per-point
payloads, NOT class channels — the grid itself stays single-channel, so vocab
size never touches grid memory).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.engine import ActiveSearcher, ExecutionPlan
from repro.core.grid import GridConfig, GridIndex, build_index
from repro.core.projection import Projection, pca_projection


@dataclasses.dataclass(frozen=True)
class KNNLMConfig:
    k: int = 16
    lam: float = 0.25        # interpolation weight on the kNN distribution
    temperature: float = 1.0  # distance softmax temperature
    # HOW datastore searches execute (backend, interpret, chunked streaming)
    # — one ExecutionPlan instead of loose backend=/chunk_size= fields
    plan: ExecutionPlan = ExecutionPlan()
    grid: GridConfig = dataclasses.field(
        default_factory=lambda: GridConfig(
            grid_size=1024, tile=16, window=32, row_cap=32, r0=8, k_slack=4.0
        )
    )


def build_datastore(
    keys: jax.Array, next_tokens: jax.Array, cfg: KNNLMConfig, proj: Projection | None = None
) -> GridIndex:
    """keys: (N, d) hidden states; next_tokens: (N,) int32 payload tokens."""
    if proj is None:
        proj = pca_projection(keys, grid_dim=2)
    return build_index(keys, cfg.grid, proj, labels=next_tokens.astype(jnp.int32))


def extend_datastore(
    index: GridIndex, cfg: KNNLMConfig, keys: jax.Array, next_tokens: jax.Array
) -> GridIndex:
    """Grow the datastore ONLINE with fresh (hidden, next-token) pairs.

    Serving harvests these from its own decode stream (`launch/lm_serve.py
    --knn-online`): the new keys are projected with the datastore's EXISTING
    projection (no PCA re-fit — keys far outside the fitted extents clamp to
    the grid edge, which active search tolerates) and delta-applied via
    `core.mutable` instead of rebuilding the index.

    This one-shot helper re-opens the slack layout each call; a caller that
    grows REPEATEDLY should hold the state across batches instead (an
    `ActiveSearcher` handle via `.insert`, or a `core.mutable.MutableIndex`
    directly, as lm_serve's Engine does)."""
    from repro.core import mutable as mut

    state = mut.from_index(index, cfg.grid)
    state = mut.insert(
        state, cfg.grid, keys, labels=jnp.asarray(next_tokens, jnp.int32)
    )
    return mut.snapshot(state, cfg.grid)


@partial(jax.jit, static_argnames=("cfg", "vocab_size"))
def knn_logprobs(
    index: GridIndex, cfg: KNNLMConfig, hidden: jax.Array, vocab_size: int
) -> jax.Array:
    """log p_knn over the vocab.  hidden: (B, d) -> (B, vocab)."""
    searcher = ActiveSearcher.from_index(index, cfg.grid, plan=cfg.plan)
    res = searcher.search(hidden, cfg.k, mode="refined")
    w = jnp.where(res.valid, -res.dists / cfg.temperature, -jnp.inf)
    w = jax.nn.softmax(w, axis=-1)                    # (B, k)
    w = jnp.where(res.valid, w, 0.0)
    tok = jnp.clip(res.labels, 0, vocab_size - 1)

    def scatter(wi, ti):
        return jnp.zeros((vocab_size,), jnp.float32).at[ti].add(wi)

    p = jax.vmap(scatter)(w, tok)                     # (B, vocab)
    # A query can retrieve NOTHING (sparse datastore, empty candidate
    # window): softmax over all -inf is nan and the scatter leaves p == 0.
    # No evidence -> the uninformative distribution, so p_knn stays a
    # normalized distribution for every lane and interpolation stays finite.
    any_valid = jnp.any(res.valid, axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 1.0 / vocab_size)
    return jnp.log(jnp.maximum(p, 1e-20))


@partial(jax.jit, static_argnames=("cfg",))
def interpolate(
    lm_logits: jax.Array, knn_logp: jax.Array, cfg: KNNLMConfig
) -> jax.Array:
    """log( lam * p_knn + (1-lam) * p_lm ), numerically via logaddexp."""
    lm_logp = jax.nn.log_softmax(lm_logits, axis=-1)
    return jnp.logaddexp(
        jnp.log(cfg.lam) + knn_logp, jnp.log1p(-cfg.lam) + lm_logp
    )


def knn_lm_logits(
    index: GridIndex, cfg: KNNLMConfig, hidden: jax.Array, lm_logits: jax.Array
) -> jax.Array:
    """One-call API used by launch/lm_serve.py's Engine."""
    knn_lp = knn_logprobs(index, cfg, hidden, lm_logits.shape[-1])
    return interpolate(lm_logits, knn_lp, cfg)
