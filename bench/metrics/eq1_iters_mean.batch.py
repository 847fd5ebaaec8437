"""Mean Eq.-1 radius-loop iterations per served query (SearchResult.iters)."""

import numpy as np


def read(rec):
    it = rec["served"].get("iters")
    return None if it is None or not len(it) else float(np.mean(it))
