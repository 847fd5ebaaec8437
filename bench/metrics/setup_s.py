"""Seconds from process start to the first timed request: data, projection,
build, warm-up (and compilation, where the cache misses)."""


def read(rec):
    return rec["setup_s"]
