# Pallas TPU kernels for the paper's compute hot-spots (+ jnp oracles).
#   tile_count          — circle-masked pyramid-tile count (the paper's inner loop)
#   candidate_topk      — fused candidate distance + streaming top-k (dense input)
#   csr_candidate_topk  — fused CSR gather + distance + top-k straight from the
#                         sorted point store (no (B, w*row_cap, d) intermediate)
#   brute_knn           — blocked exact kNN baseline (streaming top-k on MXU)
# ops.py = jit'd wrappers (interpreted on the CPU, Mosaic on a TPU; see
# ops.resolve_interpret), ref.py = pure-jnp oracles, rank.py = the shared
# fixed-order candidate distances and in-kernel top-k.

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
