"""Real (unpadded) rows per search batch in the window, from the queue's
own counters: (batch_rows - pad_rows) / batches."""


def read(rec):
    s = rec["batcher"]
    if not s["batches"]:
        return None
    return (s["batch_rows"] - s["pad_rows"]) / s["batches"]
