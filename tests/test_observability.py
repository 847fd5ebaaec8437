"""What the program records about itself: the serving queue's spans and
counters (`launch/serve.DynamicBatcher`), the search programs' named scopes
(`core/batched.py`) and the kernels' names.

Spans are `jax.profiler.TraceAnnotation`s and land in a profiler trace on
the host's line; scopes land in the HLO metadata (`op_name`) of the
compiled search programs, where a device trace's ops are mapped to them.
"""

from __future__ import annotations

import contextlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import batched
from repro.core import quantized as qz
from repro.core.grid import GridConfig, build_index
from repro.core.projection import identity_projection
from repro.kernels import ops
from repro.kernels.csr_candidate_topk_q8 import q8_store_rows
from repro.launch.serve import DynamicBatcher, _pow2

QCFG = GridConfig(grid_size=64, tile=8, n_classes=3, window=16, row_cap=8,
                  r0=4, k_slack=2.0)
PHASES = ("assemble", "dispatch", "sync", "resolve")
SCOPES = ("search.project", "search.radius_loop", "search.window",
          "search.candidates", "search.records")


def _searcher(backend="jnp", n=512, seed=0):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 3, size=n), jnp.int32)
    return api.ActiveSearcher.from_index(
        build_index(pts, QCFG, identity_projection(pts), labels=labels), QCFG,
        plan=api.ExecutionPlan(backend=backend),
    )


def _queries(n, seed=1):
    return np.random.default_rng(seed).normal(size=(n, 2)).astype(np.float32)


# ------------------------------------------------------ queue: counters ----


def test_queue_phase_counters_cover_the_batch():
    """Each phase's counter is at most the batch's, and together the
    phases hold (nearly) all of it: only the bookkeeping between them is
    left out."""
    q = DynamicBatcher(_searcher(), k=5)
    for _ in range(3):
        for n in (1, 3, 2):
            q.submit(_queries(n))
        q.drain()
    st = q.stats
    assert st["batches"] == 3
    assert st["batch_ns"] > 0
    for phase in PHASES:
        assert 0 < st[f"{phase}_ns"] <= st["batch_ns"], phase
    assert sum(st[f"{p}_ns"] for p in PHASES) >= 0.9 * st["batch_ns"]
    assert st["insert_ns"] == 0


def test_queue_wait_is_exact_on_a_fixed_clock(monkeypatch):
    """wait_ns adds, per request, batch start minus submit time: with the
    host clock held at set values, it is exact and every phase reads 0."""
    now = [1_000]
    monkeypatch.setattr(time, "perf_counter_ns", lambda: now[0])
    q = DynamicBatcher(_searcher(), k=5)
    q.submit(_queries(1))        # at 1,000
    now[0] = 1_250
    q.submit(_queries(2))        # at 1,250
    now[0] = 5_000               # the batch starts here
    q.drain()
    assert q.stats["wait_ns"] == (5_000 - 1_000) + (5_000 - 1_250)
    assert q.stats["batch_ns"] == 0
    assert all(q.stats[f"{p}_ns"] == 0 for p in PHASES)
    now[0] = 7_000
    q.submit(_queries(1))        # a second batch adds its own wait
    now[0] = 7_500
    q.drain()
    assert q.stats["wait_ns"] == 7_750 + 500


def test_queue_insert_counter_and_no_per_request_state():
    """An insert drain is timed under insert_ns; every stat is an integer,
    so the queue keeps nothing that grows with the requests it served."""
    q = DynamicBatcher(_searcher("pallas"), k=5)
    q.offer_insert(jnp.asarray(_queries(8)),
                   labels=jnp.zeros((8,), jnp.int32))
    q.drain()
    assert q.stats["inserts_applied"] == 8
    assert q.stats["insert_ns"] > 0
    for _ in range(4):
        q.submit(_queries(1))
    q.drain()
    assert all(isinstance(v, int) for v in q.stats.values()), q.stats


def _padded_nbytes(out) -> int:
    return sum(np.asarray(a).nbytes for a in jax.tree.leaves(out))


@pytest.mark.parametrize("op", ["search", "classify"])
def test_queue_sync_bytes_count_the_padded_outputs(op):
    """`sync_bytes` adds, per batch, the bytes of the whole padded result
    (pad rows included), copied to the host once: two batches of 5 and 2
    rows, padded to 8 and 2, read as a direct call at those sizes does."""
    s = _searcher()
    call = s.search if op == "search" else s.classify
    q = DynamicBatcher(s, k=5)
    want = 0
    for sizes in ((3, 2), (2,)):
        rows = [_queries(n, seed=n) for n in sizes]
        for r in rows:
            q.submit(r, op=op)
        q.drain()
        qs = np.concatenate(rows)
        qs = np.concatenate(
            [qs, np.repeat(qs[-1:], _pow2(len(qs)) - len(qs), axis=0)])
        want += _padded_nbytes(call(jnp.asarray(qs), 5))
    assert q.stats["batches"] == 2
    assert q.stats["sync_bytes"] == want > 0


def test_queue_copies_each_batch_to_the_host_once(monkeypatch):
    """The one device-to-host copy of a batch runs in its `sync` phase, and
    `resolve` hands out host arrays: no device array reaches a future."""
    q = DynamicBatcher(_searcher(), k=5)
    phases, copies = [], []
    real_phase, real_get = q._phase, jax.device_get

    @contextlib.contextmanager
    def phase(name):
        phases.append(name)
        with real_phase(name):
            yield
        phases.pop()

    def device_get(x):
        copies.append(phases[-1] if phases else None)
        return real_get(x)

    monkeypatch.setattr(q, "_phase", phase)
    monkeypatch.setattr(jax, "device_get", device_get)
    futs = []
    for sizes, op in (((1, 3, 2), "search"), ((2, 1), "classify"),
                      ((4,), "search")):
        futs += [q.submit(_queries(n), op=op) for n in sizes]
        q.drain()
    assert q.stats["batches"] == 3
    assert copies == ["sync"] * 3
    for fut in futs:
        leaves = jax.tree.leaves(fut.result(timeout=0))
        assert leaves and all(type(a) is np.ndarray for a in leaves)


# --------------------------------------------------------- queue: spans ----


def _host_events(trace_dir):
    from pathlib import Path

    from jax.profiler import ProfileData

    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    (plane,) = [p for p in pd.planes if p.name == "/host:CPU"]
    for line in plane.lines:
        events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                   dict(ev.stats)) for ev in line.events]
        if any(name == "queue.batch" for name, *_ in events):
            return events
    return []


def test_profiled_queue_spans_nest_under_the_batch(tmp_path):
    """In a profiled run every batch is a `queue.batch` span holding its
    sequence number and row count, with the four phases inside it in
    order, and the searcher's own `search.call` inside the dispatch."""
    q = DynamicBatcher(_searcher(), k=5)
    q.submit(_queries(2))
    q.drain()                                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    for n in (3, 1):
        q.submit(_queries(n))
        q.drain()
    jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    batches = [e for e in events if e[0] == "queue.batch"]
    assert [(b[3]["seq"], b[3]["rows"]) for b in batches] == [(1, 3), (2, 1)]
    for _, b0, b1, _ in batches:
        inside = sorted((s, name) for name, s, e, _ in events
                        if name.startswith(("queue.", "search.call"))
                        and name != "queue.batch" and b0 <= s and e <= b1)
        assert [name for _, name in inside] == [
            "queue.assemble", "queue.dispatch", "search.call", "queue.sync",
            "queue.resolve"]


def test_profiled_classify_batch_runs_sync_and_resolve(tmp_path):
    """A classify batch runs the same four phases as a search batch: its
    host copy under `queue.sync`, its slices under `queue.resolve`."""
    q = DynamicBatcher(_searcher(), k=5)
    q.submit(_queries(2), op="classify")
    q.drain()                                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    q.submit(_queries(1), op="classify")
    q.submit(_queries(1), op="classify")
    q.drain()
    jax.profiler.stop_trace()

    events = _host_events(tmp_path)
    (batch,) = [e for e in events if e[0] == "queue.batch"]
    assert (batch[3]["seq"], batch[3]["rows"]) == (1, 2)
    inside = sorted((s, name) for name, s, e, _ in events
                    if name.startswith(("queue.", "search.call"))
                    and name != "queue.batch"
                    and batch[1] <= s and e <= batch[2])
    assert [name for _, name in inside] == [
        "queue.assemble", "queue.dispatch", "search.call", "queue.sync",
        "queue.resolve"]


# ------------------------------------------------ search programs: scopes --


def _op_names(hlo_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _scopes_in(hlo_text: str) -> set[str]:
    return {s for s in SCOPES
            if any(f"/{s}/" in n or n.endswith(f"/{s}")
                   for n in _op_names(hlo_text))}


@pytest.fixture(scope="module")
def pallas_searcher():
    return _searcher("pallas")


def test_search_program_stages_are_scoped(pallas_searcher):
    """The optimized HLO of the pallas search program names each of its
    five stages; the radius loop's `while` sits under its scope."""
    s = pallas_searcher
    q = jnp.asarray(_queries(8))
    txt = batched._search_impl.lower(
        s.index, s.cfg, q, 5, "refined", None,
        batched.get_candidate_pipeline("fused"), None, False,
    ).compile().as_text()
    assert _scopes_in(txt) == set(SCOPES)
    assert any(n.endswith("/search.radius_loop/while")
               for n in _op_names(txt))


def test_q8_search_program_stages_are_scoped(pallas_searcher):
    s = pallas_searcher
    q = jnp.asarray(_queries(8))
    store = qz.quantize_index(s.index, s.cfg)
    txt = batched._search_q8_impl.lower(
        s.index, store, s.cfg, q, 5, 16, "refined", None, None, False,
    ).compile().as_text()
    assert _scopes_in(txt) == set(SCOPES)


def test_classify_program_votes_under_records(pallas_searcher):
    s = pallas_searcher
    q = jnp.asarray(_queries(8))
    txt = batched._classify_impl.lower(
        s.index, s.cfg, q, 5, "refined", None,
        batched.get_candidate_pipeline("fused"), None, False,
    ).compile().as_text()
    assert _scopes_in(txt) == set(SCOPES)


def test_scopes_leave_results_unchanged(pallas_searcher):
    """The scoped pallas path still answers as the jnp reference does."""
    q = jnp.asarray(_queries(16))
    got = pallas_searcher.search(q, 5)
    want = pallas_searcher.with_plan(backend="jnp").search(q, 5)
    for f in api.SearchResult._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), f)


# ---------------------------------------------------------- kernel names ---

N, D, K, B, W, RC = 4096, 128, 10, 8, 32, 32


def _kernel_cases():
    cfg = GridConfig(grid_size=4096, window=W, row_cap=RC)
    n_tiles = sum(nb * nb for nb in cfg.level_nblks)
    rows = q8_store_rows(N, RC)
    return {
        "tile_count_multilevel": (
            lambda t, q, r, lv: ops.tile_count_multilevel(
                t, q, r, lv, cfg.tile, cfg.level_nblks, interpret=False),
            (((n_tiles, cfg.n_channels, cfg.tile, cfg.tile), jnp.int32),
             ((B, 2), jnp.float32), ((B,), jnp.float32), ((B,), jnp.int32))),
        "csr_candidate_topk": (
            lambda st, s, e, q: ops.csr_candidate_topk(
                st, s, e, q, K, N, RC, interpret=False),
            (((N, D), jnp.float32), ((B, W), jnp.int32), ((B, W), jnp.int32),
             ((B, D), jnp.float32))),
        "csr_shortlist_q8": (
            lambda st, sc, s, e, q: ops.csr_shortlist_q8(
                st, sc, s, e, q, 4 * K, N, RC, interpret=False),
            (((rows, D), jnp.int8), ((rows,), jnp.float32),
             ((B, W), jnp.int32), ((B, W), jnp.int32), ((B, D), jnp.float32))),
        "candidate_topk": (
            lambda c, v, q: ops.candidate_topk(c, v, q, K, d_chunk=D,
                                               interpret=False),
            (((B, 4 * K, D), jnp.float32), ((B, 4 * K), jnp.bool_),
             ((B, D), jnp.float32))),
    }


@pytest.mark.parametrize("name", ["tile_count_multilevel",
                                  "csr_candidate_topk", "csr_shortlist_q8",
                                  "candidate_topk"])
def test_main_path_kernels_are_named(name):
    """Each main-path `pallas_call` carries its own name into the Mosaic
    custom call (lowered for a TPU, without one), where an unnamed call
    would read `_kernel`."""
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    txt = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "([^"]*)"', txt) == [name]
