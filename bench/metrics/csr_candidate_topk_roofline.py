"""The candidate stage's share of the HBM roofline, in %: the float32
candidate rows the window contract needs for every query served in the
traced window (`reference.window_bytes`: the first row_cap points of each
window row's span), over the chip's HBM bandwidth (`peaks.json`), over the
device time under `csr_candidate_topk`."""

import trace_reduce

NEEDS = ("candidate_bytes",)
SCOPE = "csr_candidate_topk"


def read(rec):
    tr, peaks = rec["trace"], rec["peaks"]
    if tr is None or peaks is None:
        return None
    s = trace_reduce.scope_seconds(tr, SCOPE)
    if s <= 0:
        return None
    return rec["candidate_bytes"] / peaks["hbm_bytes_per_s"] / s * 100
