"""Closed loop: `clients` callers, each sending its next request only when
the last one is answered (ann-benchmarks' batch mode with one client).

Each request is `request_rows` queries taken in order from the pool,
wrapping, from an offset drawn from the seed.  A request is due when it is
sent.  The loop stops sending once `seconds` have passed and the window
ends with the last answer, so a rate over it covers all the work.
"""

from __future__ import annotations

import time

import numpy as np

import serving


def warm(batcher, pool: np.ndarray, traffic: dict) -> None:
    """Every batch shape the window will use: one request of request_rows."""
    rows = np.arange(traffic["request_rows"]) % pool.shape[0]
    fut = serving.submit(batcher, pool[rows])
    batcher.drain()
    serving.fetch(fut)


def run(batcher, pool: np.ndarray, traffic: dict, seconds: float,
        rng: np.random.Generator) -> dict:
    if traffic.get("clients", 1) != 1:
        raise ValueError("the closed loop drives one client")
    per = traffic["request_rows"]
    q = pool.shape[0]
    nxt = int(rng.integers(q))
    log = serving.RequestLog()
    step = serving.Stepper(batcher)
    with serving.window():
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            rows = (nxt + np.arange(per)) % q
            nxt = int((nxt + per) % q)
            due = time.perf_counter()
            fut = serving.submit(batcher, pool[rows])
            log.attempted += 1
            ts = time.perf_counter()
            step()
            td = time.perf_counter()
            log.add(due, ts, td, rows, serving.fetch(fut))
        t1 = time.perf_counter()
    return log.record(t0, t1)
