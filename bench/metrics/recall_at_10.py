"""recall@10 of every answer served in the window, against the benchmark's
own brute force over the query pool (`reference.exact_knn`)."""

import numpy as np

NEEDS = ("ground_truth",)


def read(rec):
    got = rec["served"]["ids"][:, :10]
    want = rec["ground_truth"][rec["pool_rows"]][:, :10]
    hits = (got[:, :, None] == want[:, None, :]) & (got[:, :, None] >= 0)
    return float(hits.any(axis=2).sum()) / want.size
