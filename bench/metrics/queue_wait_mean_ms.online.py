"""Mean time (ms) a request waited in the queue, from `submit` to the start
of the batch that served it: the queue's own `wait_ns` counter over the
requests of the window (`DynamicBatcher.stats`)."""


def read(rec):
    s = rec["batcher"]
    if "wait_ns" not in s or not s["requests"]:
        return None
    return s["wait_ns"] / s["requests"] / 1e6
