"""Share (%) of the traced window in which no operation ran on the device."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return (1 - tr["busy_s"] / tr["window_s"]) * 100
