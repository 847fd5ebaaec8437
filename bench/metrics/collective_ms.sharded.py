"""Device ms per search batch, averaged over the chips, of the cross-chip
operations (`all-gather`, `all-reduce`, `collective-permute`,
`all-to-all`, `reduce-scatter`, and their asynchronous `-start`/`-done`
halves): the sharded store's merge of the per-shard top-k lists."""

PREFIXES = ("all-gather", "all-reduce", "collective-permute", "all-to-all",
            "reduce-scatter")


def read(rec):
    tr, batches = rec["trace"], rec["batcher"]["batches"]
    if tr is None or not batches:
        return None
    s = sum(v for k, v in tr["op_seconds"].items() if k.startswith(PREFIXES))
    return s / batches * 1e3 if s > 0 else None
