"""Queries answered in the window over the window's seconds (host clock),
in a cell offered more than it can serve: the window runs until the last
request offered is answered, so every request and the whole drain count."""


def read(rec):
    return len(rec["pool_rows"]) / (rec["t1"] - rec["t0"])
