"""Host ms per search batch spent handing out results (each request's
slice of every result field, and `set_result`), from the queue's own
counter: resolve_ns / batches."""


def read(rec):
    s = rec["batcher"]
    if "resolve_ns" not in s or not s["batches"]:
        return None
    return s["resolve_ns"] / s["batches"] / 1e6
