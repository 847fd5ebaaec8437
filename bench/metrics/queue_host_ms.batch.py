"""Host ms per search batch that the queue spends outside the device wait:
assembling the batch (coalescing, padding, the host-to-device put),
dispatching the search and handing out the results, from the queue's own
counters: (assemble_ns + dispatch_ns + resolve_ns) / batches."""

PHASES = ("assemble_ns", "dispatch_ns", "resolve_ns")


def read(rec):
    s = rec["batcher"]
    if not all(p in s for p in PHASES) or not s["batches"]:
        return None
    return sum(s[p] for p in PHASES) / s["batches"] / 1e6
