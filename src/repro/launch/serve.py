"""The index's serving queue: `DynamicBatcher`, dynamic batching of search
and classify requests over one `ActiveSearcher`, with datastore inserts
queued between batches.

It imports nothing of the LM stack; the kNN-LM generation engine that
drives it from a decode stream lives in `launch/lm_serve.py`.
"""

from __future__ import annotations

import collections
import contextlib
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import api


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class DynamicBatcher:
    """Async request queue with dynamic batching over one `ActiveSearcher`.

    Requests (`submit`) are coalesced into batches padded up to the next
    power of two — the SAME pow2 ladder the jitted cores already compile
    for (core/mutable.py pads insert batches identically), so a ragged
    request stream hits a handful of cached executables instead of one
    retrace per batch size.  Pad rows replicate the last real query and are
    sliced off before a request's future resolves: results are bit-identical
    to an unpadded call (tests/test_padding.py) and pads never leak into the
    queue's truncation stats.

    Answers are on the host.  Each batch's whole padded result is copied to
    the host once, and every request's rows are numpy slices (views) of that
    copy, so a future resolves to a `SearchResult` of `np.ndarray` fields
    (or an `np.ndarray` for classify) and no device work runs per request.
    A caller that feeds an answer back into a device computation passes the
    numpy arrays to jnp as they are.

    `offer_insert` queues datastore growth (`lm_serve.py --knn-online`)
    instead of applying it inline; the backlog drains BETWEEN search batches (`step`
    alternates: one search batch, then any queued inserts), so a decode
    stream never waits on an insert mid-batch, and compaction pauses land
    on the batch boundary.  `stats` tracks the backlog depth, pad overhead
    and truncation, and where each batch's host time goes.

    Every batch runs under a `jax.profiler.TraceAnnotation` named
    `queue.batch` (with its sequence number `seq` and real row count
    `rows`), whose phases are spans of their own: `queue.assemble`
    (coalescing, padding, the host-to-device put), `queue.dispatch` (the
    searcher call, asynchronous), `queue.sync` (the one host copy of the
    batch's result, where the host waits on the device), `queue.resolve`
    (host-only numpy slicing per request and `set_result`); an insert drain
    runs under `queue.insert`.  Spans are recorded while the profiler
    traces.  The integer counters `batch_ns`, `assemble_ns`, `dispatch_ns`,
    `sync_ns`, `resolve_ns` and `insert_ns` add up the same intervals on
    the host clock, always; `wait_ns` adds, per request, the time from
    `submit` to the start of the batch that serves it, and `sync_bytes` the
    bytes each batch's host copy moved.
    """

    def __init__(self, searcher, k: int, max_batch: int = 64):
        if max_batch <= 0:
            raise ValueError(f"max_batch must be positive, got {max_batch}")
        searcher.check_plan()  # e.g. refuses the Pallas interpreter on a TPU
        self.searcher = searcher
        self.k = k
        self.max_batch = max_batch
        self._requests: collections.deque = collections.deque()
        self._inserts: collections.deque = collections.deque()
        self._after_search = False  # drain inserts before the next batch
        self.stats = {
            "requests": 0, "request_rows": 0, "batches": 0, "batch_rows": 0,
            "pad_rows": 0, "truncated_rows": 0, "insert_rows_queued": 0,
            "insert_backlog": 0, "insert_backlog_peak": 0,
            "inserts_applied": 0, "wait_ns": 0, "batch_ns": 0,
            "assemble_ns": 0, "dispatch_ns": 0, "sync_ns": 0,
            "resolve_ns": 0, "insert_ns": 0, "sync_bytes": 0,
        }

    # ------------------------------------------------------------- enqueue --
    def submit(self, queries, op: str = "search") -> Future:
        """Queue a (Q, d) request; the future resolves to a `SearchResult`
        (op="search") or (Q,) predictions (op="classify") for exactly the
        submitted rows, held on the host as `np.ndarray`s."""
        if op not in ("search", "classify"):
            raise ValueError(f"op must be 'search' or 'classify', got {op!r}")
        q = np.asarray(queries)
        if q.ndim != 2 or q.shape[0] == 0:
            raise ValueError(f"queries must be (Q>0, d), got {q.shape}")
        fut: Future = Future()
        self._requests.append((op, q, fut, time.perf_counter_ns()))
        self.stats["requests"] += 1
        self.stats["request_rows"] += q.shape[0]
        return fut

    def offer_insert(self, points, labels=None, ids=None) -> int:
        """Queue datastore growth; applied between search batches (or by
        `drain`).  Returns the current insert backlog depth in rows."""
        self._inserts.append((points, labels, ids))
        self.stats["insert_rows_queued"] += int(points.shape[0])
        backlog = sum(int(p.shape[0]) for p, _, _ in self._inserts)
        self.stats["insert_backlog"] = backlog
        self.stats["insert_backlog_peak"] = max(
            self.stats["insert_backlog_peak"], backlog
        )
        return backlog

    # -------------------------------------------------------------- serve ---
    def step(self) -> bool:
        """Run ONE unit of work: the insert backlog if a search batch just
        ran (or nothing else is queued), else one dynamic search batch.
        Returns False when both queues are empty."""
        if self._inserts and (self._after_search or not self._requests):
            self._apply_inserts()
            self._after_search = False
            return True
        if not self._requests:
            return False
        self._run_batch()
        self._after_search = True
        return True

    def drain(self) -> None:
        """Serve until both the request and insert queues are empty."""
        while self.step():
            pass

    async def run_async(self, poll_s: float = 0.001) -> None:
        """Cooperative serving loop for an asyncio host: steps whenever work
        is queued, yields to the event loop when idle.  Cancel to stop."""
        import asyncio

        while True:
            if not self.step():
                await asyncio.sleep(poll_s)

    # ------------------------------------------------------------ internals -
    @contextlib.contextmanager
    def _phase(self, name: str):
        """Span `queue.<name>`, and its host time added to `<name>_ns`."""
        t0 = time.perf_counter_ns()
        with TraceAnnotation(f"queue.{name}"):
            yield
        self.stats[f"{name}_ns"] += time.perf_counter_ns() - t0

    def _apply_inserts(self) -> None:
        with self._phase("insert"):
            rows = 0
            while self._inserts:
                pts, labels, ids = self._inserts.popleft()
                self.searcher = self.searcher.insert(pts, labels=labels,
                                                     ids=ids)
                rows += int(pts.shape[0])
        self.stats["inserts_applied"] += rows
        self.stats["insert_backlog"] = 0

    def _next_batch(self) -> tuple[int, int]:
        """(requests, rows) of the next batch: the leading requests of one
        op, up to max_batch rows."""
        op = self._requests[0][0]
        count = rows = 0
        for req_op, q, _, _ in self._requests:
            if req_op != op or rows >= self.max_batch:
                break
            count += 1
            rows += q.shape[0]
        return count, rows

    def _run_batch(self) -> None:
        count, n = self._next_batch()
        with TraceAnnotation("queue.batch", seq=self.stats["batches"], rows=n):
            t_start = time.perf_counter_ns()
            with self._phase("assemble"):
                batch = [self._requests.popleft() for _ in range(count)]
                op = batch[0][0]
                qs = np.concatenate([b[1] for b in batch], axis=0)
                pad = _pow2(n) - n
                if pad:
                    qs = np.concatenate(
                        [qs, np.repeat(qs[-1:], pad, axis=0)], axis=0)
                qj = jnp.asarray(qs, jnp.float32)
            with self._phase("dispatch"):
                if op == "search":
                    out = self.searcher.search(qj, self.k)
                else:
                    out = self.searcher.classify(qj, self.k)
            with self._phase("sync"):
                host = jax.device_get(out)
                self.stats["sync_bytes"] += sum(
                    a.nbytes for a in jax.tree.leaves(host))
                if op == "search":
                    self.stats["truncated_rows"] += int(
                        host.truncated[:n].sum())
            with self._phase("resolve"):
                ofs = 0
                for _, q, fut, _ in batch:
                    m = q.shape[0]
                    if op == "search":
                        fut.set_result(api.SearchResult._make(
                            a[ofs:ofs + m] for a in host))
                    else:
                        fut.set_result(host[ofs:ofs + m])
                    ofs += m
            self.stats["batch_ns"] += time.perf_counter_ns() - t_start
        self.stats["wait_ns"] += sum(t_start - b[3] for b in batch)
        self.stats["batches"] += 1
        self.stats["batch_rows"] += n
        self.stats["pad_rows"] += pad
