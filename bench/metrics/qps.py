"""Queries answered in the window over the window's seconds (host clock)."""


def read(rec):
    return len(rec["pool_rows"]) / (rec["t1"] - rec["t0"])
