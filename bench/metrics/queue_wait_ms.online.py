"""Median time (ms, host clock) from when a request was due to the start of
the `DynamicBatcher.step` that served it."""

import numpy as np


def read(rec):
    return float(np.median(rec["step_start"] - rec["due"])) * 1e3
