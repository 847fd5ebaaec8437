"""Peak device bytes in use after the window over the points stored per
chip (`memory_stats()["peak_bytes_in_use"]`)."""


def read(rec):
    if rec["peak_bytes"] is None:
        return None
    return rec["peak_bytes"] / rec["n_points"]
