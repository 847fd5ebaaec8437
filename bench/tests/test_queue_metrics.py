"""The serving queue's per-layer metrics, read from the queue's own
counters (`DynamicBatcher.stats`, diffed over the window into
`rec["batcher"]` by the harness)."""

from __future__ import annotations

import pytest

from conftest import BENCH
from harness import load_module

NAMES = ("queue_wait_mean_ms.online", "queue_host_ms.online",
         "queue_resolve_ms.online", "queue_host_ms.batch")


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_readers_by_hand():
    rec = {"batcher": {
        "requests": 8, "batches": 4, "wait_ns": 16_000_000,
        "batch_ns": 40_000_000, "assemble_ns": 2_000_000,
        "dispatch_ns": 6_000_000, "sync_ns": 20_000_000,
        "resolve_ns": 10_000_000, "insert_ns": 0}}
    assert _reader("queue_wait_mean_ms.online").read(rec) == 2.0
    assert _reader("queue_host_ms.online").read(rec) == 4.5
    assert _reader("queue_host_ms.batch").read(rec) == 4.5
    assert _reader("queue_resolve_ms.online").read(rec) == 2.5


@pytest.mark.parametrize("name", NAMES)
def test_readers_give_nothing_without_the_counters(name):
    """A queue without the timing counters (an older program), or a window
    with no batch, reads as no value rather than an error."""
    old = {"requests": 8, "request_rows": 8, "batches": 4, "batch_rows": 8,
           "pad_rows": 0, "truncated_rows": 0}
    assert _reader(name).read({"batcher": old}) is None
    empty = dict(old, requests=0, batches=0, wait_ns=0, assemble_ns=0,
                 dispatch_ns=0, resolve_ns=0)
    assert _reader(name).read({"batcher": empty}) is None


def test_readers_on_a_served_window():
    """Counters diffed over a window of a real queue, as the harness does,
    read back as positive milliseconds; the queue's phases hold the host
    time that the host metric reads."""
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core.grid import GridConfig, build_index
    from repro.core.projection import identity_projection
    from repro.launch.serve import DynamicBatcher

    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=8, r0=4)
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.normal(size=(256, 2)), jnp.float32)
    q = DynamicBatcher(api.ActiveSearcher.from_index(
        build_index(pts, cfg, identity_projection(pts)), cfg), k=4)
    q.submit(rng.normal(size=(1, 2)))
    q.drain()                                       # warm-up, left out
    before = {k: v for k, v in q.stats.items() if isinstance(v, int)}
    for _ in range(3):
        for _ in range(2):
            q.submit(rng.normal(size=(1, 2)))
        q.drain()
    rec = {"batcher": {k: q.stats[k] - v for k, v in before.items()}}
    assert rec["batcher"]["batches"] == 3
    host = _reader("queue_host_ms.online").read(rec)
    resolve = _reader("queue_resolve_ms.online").read(rec)
    assert 0 < resolve < host
    assert host <= rec["batcher"]["batch_ns"] / 3 / 1e6
    assert _reader("queue_wait_mean_ms.online").read(rec) > 0
