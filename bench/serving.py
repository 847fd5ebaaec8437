"""What every load driver shares: host spans around each call into the
serving queue, and the log of requests served in the window.

Spans are `jax.profiler.TraceAnnotation`s, so a traced run puts them on the
same clock as the device's operations (`trace_reduce.py`):

- `window`: the measured window;
- `submit`: `DynamicBatcher.submit`;
- `step.search` / `step.insert`: one `DynamicBatcher.step`, by what it runs;
- `result`: the client copying a resolved answer's ids and distances to the
  host.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.profiler import TraceAnnotation

FIELDS = ("ids", "dists", "valid", "radius", "count", "iters", "converged",
          "truncated")


def submit(batcher, rows: np.ndarray):
    with TraceAnnotation("submit"):
        return batcher.submit(rows)


def window():
    return TraceAnnotation("window")


class Stepper:
    """Runs `DynamicBatcher.step` under a span named for what the step will
    run.  The queue applies its insert backlog after a search batch, or when
    no request waits; otherwise it runs one search batch."""

    def __init__(self, batcher):
        self.batcher = batcher
        self.after_search = False

    def __call__(self) -> None:
        s = self.batcher.stats
        waiting = s["request_rows"] > s["batch_rows"] - s["pad_rows"]
        inserts = s["insert_backlog"] > 0 and (self.after_search or
                                               not waiting)
        kind = "step.insert" if inserts else "step.search"
        with TraceAnnotation(kind):
            self.batcher.step()
        self.after_search = not inserts


def fetch(fut):
    """The client's read of one answer: its ids and distances copied to the
    host.  The rest of the `SearchResult` stays on the device until the
    window has closed (`RequestLog.record`)."""
    with TraceAnnotation("result"):
        res = fut.result()
        jax.device_get((res.ids, res.dists))
    return res


class RequestLog:
    """Times (host clock, seconds) and answers of the requests in a window."""

    def __init__(self):
        self.due, self.step_start, self.done = [], [], []
        self.rows, self.answers = [], []
        self.attempted = 0

    def add(self, due: float, step_start: float, done: float,
            rows: np.ndarray, answer) -> None:
        self.due.append(due)
        self.step_start.append(step_start)
        self.done.append(done)
        self.rows.append(rows)
        self.answers.append(answer)

    def record(self, t0: float, t1: float) -> dict:
        """The window as the metrics read it; served rows concatenated in
        request order."""
        n_rows = np.array([len(r) for r in self.rows], np.int64)
        answers = jax.device_get(
            [{f: getattr(a, f) for f in FIELDS} for a in self.answers])
        served = ({f: np.concatenate([a[f] for a in answers])
                   for f in FIELDS} if answers else {})
        return {
            "t0": t0, "t1": t1,
            "due": np.asarray(self.due), "step_start": np.asarray(
                self.step_start), "done": np.asarray(self.done),
            "request_rows": n_rows,
            "pool_rows": (np.concatenate(self.rows) if self.rows
                          else np.zeros(0, np.int64)),
            "served": served,
            "attempted": self.attempted,
            "failed": self.attempted - len(self.done),
        }
