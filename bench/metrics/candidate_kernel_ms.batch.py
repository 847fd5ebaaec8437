"""Device ms per search batch of the ops traced under the jitted
`csr_candidate_topk` (the candidate stage's fused kernel)."""

import trace_reduce

SCOPE = "csr_candidate_topk"


def read(rec):
    tr, batches = rec["trace"], rec["batcher"]["batches"]
    if tr is None or not batches:
        return None
    s = trace_reduce.scope_seconds(tr, SCOPE)
    return s / batches * 1e3 if s > 0 else None
