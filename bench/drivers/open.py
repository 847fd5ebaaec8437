"""Open loop: independent users whose requests arrive on a schedule, whether
or not earlier ones have been answered.

`round(rate_per_s * seconds)` requests of `request_rows` random pool
queries each arrive over the window at Poisson times drawn from the seed,
rescaled to span the window exactly, so every seed offers the same work.
A request is timed from when it was due, so a stall counts against every
request that waits behind it.  The serving thread submits what is due,
runs one batch, and copies each answered request to the host; the window
ends when the last request is answered.
"""

from __future__ import annotations

import time

import numpy as np

import serving

SPIN_S = 0.0005  # sleep until this close to the next arrival, then spin


def warm(batcher, pool: np.ndarray, traffic: dict) -> None:
    """Every batch the window can form: 1 to max_batch waiting requests."""
    per = traffic["request_rows"]
    for n in range(1, traffic["max_batch"] // per + 1):
        futs = [serving.submit(batcher, pool[np.arange(per) % pool.shape[0]])
                for _ in range(n)]
        batcher.drain()
        for f in futs:
            serving.fetch(f)


def schedule(traffic: dict, seconds: float, n_pool: int,
             rng: np.random.Generator):
    """(arrival offsets (n,), pool rows (n, request_rows)) for one window."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    gaps = rng.exponential(1.0, n + 1)
    at = np.cumsum(gaps)[:-1] / gaps.sum() * seconds
    rows = rng.integers(0, n_pool, size=(n, traffic["request_rows"]))
    return at, rows


def run(batcher, pool: np.ndarray, traffic: dict, seconds: float,
        rng: np.random.Generator) -> dict:
    at, rows = schedule(traffic, seconds, pool.shape[0], rng)
    n = len(at)
    log = serving.RequestLog()
    step = serving.Stepper(batcher)
    pending = []  # (request index, future), in arrival order
    late = np.zeros(n)
    backlog = np.zeros(n, np.int64)  # requests waiting as each one arrives
    i = 0
    with serving.window():
        t0 = time.perf_counter()
        due = t0 + at
        while i < n or pending:
            t = time.perf_counter()
            while i < n and due[i] <= t:
                pending.append((i, serving.submit(batcher, pool[rows[i]])))
                late[i] = time.perf_counter() - due[i]
                backlog[i] = len(pending)
                log.attempted += 1
                i += 1
            if not pending:
                wait = due[i] - time.perf_counter()
                if wait > SPIN_S:
                    time.sleep(wait - SPIN_S)
                continue
            ts = time.perf_counter()
            step()
            td = time.perf_counter()
            k = 0
            while k < len(pending) and pending[k][1].done():
                k += 1
            for j, fut in pending[:k]:
                log.add(due[j], ts, td, rows[j], serving.fetch(fut))
            del pending[:k]
        t1 = time.perf_counter()
    rec = log.record(t0, t1)
    rec["generator_late_s"] = late
    rec["backlog"] = backlog
    return rec
