# Pallas TPU kernels for the paper's compute hot-spots (+ jnp oracles).
#   tile_count_multilevel — level-scheduled circle-masked pyramid-tile count
#                           (the paper's inner loop)
#   csr_candidate_topk    — fused CSR gather + distance + top-k straight from
#                           the sorted point store (no (B, w*row_cap, d)
#                           intermediate)
#   csr_candidate_topk_q8 — the int8 shortlist twin of csr_candidate_topk
#   candidate_topk        — candidate distance + streaming top-k (dense
#                           input; the q8 path's exact re-rank)
#   brute_knn             — blocked exact kNN baseline (streaming top-k on MXU)
#   flash_attention       — the LM stack's attention kernel
# ops.py = jit'd wrappers (interpreted on the CPU, Mosaic on a TPU; see
# ops.resolve_interpret), ref.py = pure-jnp oracles, rank.py = the shared
# fixed-order candidate distances and in-kernel top-k.

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
