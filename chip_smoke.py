"""Smoke run of the active-search index on a TPU, through its user entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded store on a four-chip host

One chip: an ann-benchmarks `sift-128-euclidean`-shaped deployment
(arXiv:1807.05614: 1,000,000 points of d = 128 float32, 256 queries, k = 10,
l2), made from `--seed` as a Gaussian mixture on the device.  The index is
built with `ActiveSearcher.build` on the `pallas` plan and searched through
the facade with `pallas`, `pallas_q8` and `jnp`, then with `exact`; a
`launch/serve.DynamicBatcher` serves ragged requests while 4,096 points are
inserted, and the inserted points are read back.  The compiled `pallas`
search must hold Mosaic kernels (`tpu_custom_call`), not the interpreter.

Four chips: `ActiveSearcher.build` on the `sharded` plan, which shards
4,000,000 points by grid cell over the host's 4 devices; its answer must
equal a one-chip `pallas` index's over the same points (every field, ids up
to equal distances), before and after 4,096 points are inserted; inserted
points whose window is not truncated are read back, and every device must
hold its shard.

Checks that fail exit non-zero.  Timings printed here are smoke timings of
one run, first calls included, not a benchmark.  The last line of standard
output is one JSON object: {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_POINTS = 1_000_000
N_POINTS_FOUR = 4_000_000
DIM = 128
N_QUERIES = 256
K = 10
N_INSERT = 4_096
N_READBACK = 64
N_CLUSTERS = 1_024
CLUSTER_STD = 0.3
GRID_SIZE = 4_096  # ~1 point per occupied cell at 1M points: no truncated window rows
SWAP_RTOL = 1e-6   # a pallas/jnp id swap is allowed only between distances this close
DIST_RTOL = 1e-5   # reported distance vs the Precision.HIGHEST recompute


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_data(seed: int, n: int, n_queries: int, n_insert: int):
    """Gaussian mixture made on the device: (points, queries, inserts)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        kc, kp, kq, ki = jax.random.split(key, 4)
        centers = jax.random.normal(kc, (N_CLUSTERS, DIM), jnp.float32)

        def sample(k, m):
            ka, kb = jax.random.split(k)
            lab = jax.random.randint(ka, (m,), 0, N_CLUSTERS)
            return centers[lab] + CLUSTER_STD * jax.random.normal(kb, (m, DIM))

        return sample(kp, n), sample(kq, n_queries), sample(ki, n_insert)

    return jax.block_until_ready(draw(jax.random.PRNGKey(seed)))


def recompute_dists(points, queries, ids):
    """l2 distances of each returned id, recomputed at Precision.HIGHEST."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(points, queries, ids):
        diff = queries[:, None, :] - points[jnp.maximum(ids, 0)]
        sq = jnp.einsum("bkd,bkd->bk", diff, diff,
                        precision=jax.lax.Precision.HIGHEST)
        return jnp.sqrt(sq)

    return f(points, queries, ids)


def check_dists(name, res, points, queries) -> None:
    """Every valid returned distance equals its HIGHEST recompute."""
    import numpy as np

    valid = np.asarray(res.valid)
    got = np.asarray(res.dists)
    want = np.asarray(recompute_dists(points, queries, res.ids))
    err = np.abs(got - want)[valid]
    bad = int(np.sum(err > DIST_RTOL * want[valid]))
    log(f"[{name}] distance check: {valid.sum()} valid results, "
        f"max abs err {float(err.max()) if err.size else 0.0!r}, {bad} outside "
        f"{DIST_RTOL} relative")
    check(bad == 0, f"{name}: {bad} reported distances differ from the "
                    f"Precision.HIGHEST recompute")


def compare_ids(name, got, want) -> int:
    """Same ids as `want`; a swap is allowed only where the two distances at
    that rank agree to SWAP_RTOL.  Returns the swap count."""
    import numpy as np

    gi, wi = np.asarray(got.ids), np.asarray(want.ids)
    gd, wd = np.asarray(got.dists), np.asarray(want.dists)
    check(np.array_equal(np.asarray(got.valid), np.asarray(want.valid)),
          f"{name}: valid masks differ")
    diff = gi != wi
    with np.errstate(invalid="ignore"):  # inf - inf where both are padding
        close = np.abs(gd - wd) <= SWAP_RTOL * np.abs(wd)
    bad = int(np.sum(diff & ~close))
    log(f"[{name}] {int(diff.sum())} id swaps between equal-to-{SWAP_RTOL} "
        f"distances, {bad} real differences")
    check(bad == 0, f"{name}: {bad} ids differ beyond distance ties")
    return int(diff.sum())


def recall(res, ref) -> float:
    import numpy as np

    got, want = np.asarray(res.ids), np.asarray(ref.ids)
    hits = [len(set(g[g >= 0]) & set(w[w >= 0])) for g, w in zip(got, want)]
    return float(np.sum(hits)) / want.size


def timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def readback(name, res, first_id: int) -> None:
    """Each inserted point, searched, returns its own id at distance 0."""
    import numpy as np

    ids, dists = np.asarray(res.ids[:, 0]), np.asarray(res.dists[:, 0])
    want = first_id + np.arange(ids.shape[0])
    n_ok = int(np.sum((ids == want) & (dists == 0.0)))
    log(f"[{name}] insert readback: {n_ok}/{ids.shape[0]} inserted points "
        f"return their own id at distance 0")
    check(n_ok == ids.shape[0], f"{name}: acknowledged inserts not read back")


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.core.grid import GridConfig
    from repro.launch.serve import DynamicBatcher

    dev = jax.devices()[0]
    points, queries, inserts = make_data(seed, N_POINTS, N_QUERIES, N_INSERT)
    cfg = GridConfig(grid_size=GRID_SIZE)

    # ---- load the deployment
    searcher, t_build = timed(lambda: api.ActiveSearcher.build(
        points, cfg=cfg, plan=api.ExecutionPlan(backend="pallas")))
    stats = searcher.stats()
    log(f"[build] {N_POINTS} x {DIM} points, grid {GRID_SIZE}: {t_build!r} s "
        f"(smoke timing, compiles included)")
    log(f"[build] stats {json.dumps(stats, default=str)}")
    log(f"[build] peak_bytes_in_use {peak_bytes(dev)}")

    # ---- search through the facade, then the exact reference
    res = {}
    plans = {
        "pallas": {},
        "pallas_q8": {"backend": "pallas_q8"},
        # a shortlist of every window candidate always holds the top k
        "pallas_q8_full": {"backend": "pallas_q8",
                           "rerank_k": cfg.max_candidates},
        "jnp": {"backend": "jnp"},
        "exact": {"backend": "exact"},
    }
    for name, overrides in plans.items():
        handle = searcher.with_plan(**overrides)
        res[name], t = timed(lambda: handle.search(queries, K))
        _, t2 = timed(lambda: handle.search(queries, K))
        log(f"[search {name}] {N_QUERIES} queries: first call {t!r} s, "
            f"second {t2!r} s (smoke timing, not a benchmark)")

    swaps = compare_ids("pallas vs jnp", res["pallas"], res["jnp"])
    compare_ids("pallas_q8 (full shortlist) vs pallas", res["pallas_q8_full"],
                res["pallas"])
    # the default shortlist picks the top k of a subset of the same
    # candidates with exact distances, so it can never rank better
    q8d, pd = np.asarray(res["pallas_q8"].dists), np.asarray(res["pallas"].dists)
    same = float(np.mean(np.all(
        np.asarray(res["pallas_q8"].ids) == np.asarray(res["pallas"].ids),
        axis=1)))
    log(f"[pallas_q8] default shortlist: {same!r} of queries return the "
        f"pallas ids")
    check(bool(np.all(q8d >= pd * (1 - SWAP_RTOL))),
          "pallas_q8 ranks a distance below the exact candidate top k")
    for name in ("pallas", "pallas_q8", "pallas_q8_full", "jnp"):
        check_dists(name, res[name], points, queries)
    log(f"[recall] recall@{K} vs exact: "
        + ", ".join(f"{n} {recall(res[n], res['exact'])!r}"
                    for n in ("pallas", "pallas_q8", "jnp"))
        + " (reported, not gated)")
    log(f"[search] pallas vs jnp swap count {swaps}; "
        f"peak_bytes_in_use {peak_bytes(dev)}")

    # ---- the pallas search compiled to Mosaic, not the interpreter
    hlo = jax.jit(
        lambda index, q: dataclasses.replace(searcher, index=index)
        .search(q, K).dists
    ).lower(searcher.index, queries).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled pallas search holds no Mosaic kernel")
    log("[mosaic] compiled pallas search holds tpu_custom_call")

    # ---- serve through the queue, inserting between batches
    rng = np.random.default_rng(seed)
    pool = np.asarray(queries)
    batcher = DynamicBatcher(searcher, k=K, max_batch=64)
    futures = []
    for i in range(32):
        if i == 16:
            batcher.offer_insert(inserts)
        m = int(rng.integers(1, 65))
        rows = pool[rng.integers(0, N_QUERIES, size=m)]
        futures.append((m, batcher.submit(rows)))
    batcher.drain()
    for m, fut in futures:
        out = fut.result()
        check(out.ids.shape == (m, K), "queue returned a wrong shape")
    check(batcher.stats["inserts_applied"] == N_INSERT,
          "queue did not apply the inserts")
    fut = batcher.submit(np.asarray(inserts[:N_READBACK]))
    batcher.drain()
    readback("queue", fut.result(), N_POINTS)
    st = batcher.stats
    log(f"[queue] {st['requests']} requests in {st['batches']} batches: "
        f"mean wait {st['wait_ns'] / st['requests'] / 1e9!r} s, mean batch "
        f"{st['batch_ns'] / st['batches'] / 1e9!r} s "
        f"(smoke timing with compiles, not a benchmark)")
    log(f"[queue] peak_bytes_in_use {peak_bytes(dev)}")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro import api
    from repro.core.grid import GridConfig
    from repro.core.projection import pca_projection

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs a 4-device host, found "
                          f"{len(devs)}")
    points, queries, inserts = make_data(
        seed, N_POINTS_FOUR, N_QUERIES, N_INSERT)
    cfg = GridConfig(grid_size=GRID_SIZE)
    proj = pca_projection(points)

    # the entry point every caller uses: the sharded plan shards the store
    # over every local device
    plan = api.ExecutionPlan(backend="sharded", chunk_size=256)
    searcher, t_build = timed(lambda: api.ActiveSearcher.build(
        points, cfg=cfg, plan=plan, proj=proj))
    stats = searcher.stats()
    check(stats["n_shards"] == 4 and stats["n_points"] == N_POINTS_FOUR,
          f"the sharded build holds {stats['n_shards']} shards and "
          f"{stats['n_points']} points")
    log(f"[build] {N_POINTS_FOUR} x {DIM} points over 4 devices: "
        f"{t_build!r} s (smoke timing, compiles included); shard points "
        f"{stats['shard_points']}")

    def check_placement(index, what):
        for leaf in (index.points_sorted, index.ids_sorted, index.offsets,
                     index.global_offsets):
            shards = leaf.addressable_shards
            held = sorted(s.device.id for s in shards)
            check(held == sorted(d.id for d in devs)
                  and all(s.data.shape[0] == 1 for s in shards),
                  f"{what}: a device does not hold its own shard")
        log(f"[{what}] shard placement: "
            + ", ".join(f"device {s.device.id} holds {s.data.shape} "
                        f"({s.data.nbytes} bytes)"
                        for s in index.points_sorted.addressable_shards))

    check_placement(searcher.index, "build")
    log("[memory] peak_bytes_in_use per device after the build: "
        + ", ".join(f"{d.id}: {peak_bytes(d)}" for d in devs))
    res, t = timed(lambda: searcher.search(queries, K))
    _, t2 = timed(lambda: searcher.search(queries, K))
    log(f"[search sharded] {N_QUERIES} queries: first call {t!r} s, second "
        f"{t2!r} s (smoke timing)")
    check_dists("sharded", res, points, queries)

    # the sharded answer is one index's: a one-chip pallas index over the
    # same 4M points (it fits) gives the same fields, ids up to ties
    one = api.ActiveSearcher.build(
        points, cfg=cfg, proj=proj,
        plan=api.ExecutionPlan(backend="pallas", chunk_size=256))
    want = one.search(queries, K)
    compare_ids("sharded vs one pallas index", res, want)
    for f in ("dists", "radius", "count", "iters", "converged", "truncated"):
        check(np.array_equal(np.asarray(getattr(res, f)),
                             np.asarray(getattr(want, f))),
              f"sharded vs one pallas index: {f} differs")
    log("[one index] dists, radius, count, iters, converged and truncated "
        "equal the one-chip pallas index's")

    # inserts keep it one index's answer; an inserted point lands last in
    # its cell, so one index reads it back wherever its window row holds
    # at most row_cap records (the lanes not truncated)
    grown = searcher.insert(inserts)
    check_placement(grown.index, "insert")
    got = grown.search(inserts[:N_READBACK], K)
    compare_ids("grown: sharded vs one pallas index", got,
                one.insert(inserts).search(inserts[:N_READBACK], K))
    del one
    open_ = ~np.asarray(got.truncated)
    own = ((np.asarray(got.ids[:, 0]) == N_POINTS_FOUR + np.arange(N_READBACK))
           & (np.asarray(got.dists[:, 0]) == 0.0))
    log(f"[sharded] insert readback: {int(own.sum())}/{N_READBACK} inserted "
        f"points return their own id at distance 0; {int(open_.sum())} "
        f"windows not truncated")
    check(bool(np.all(own[open_])),
          "sharded: acknowledged inserts not read back")
    log("[memory] peak_bytes_in_use per device: "
        + ", ".join(f"{d.id}: {peak_bytes(d)}" for d in devs))


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded store over four chips")
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.utils.compile_cache import cache_entries, enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    events: dict[str, int] = {}
    jax.monitoring.register_event_listener(
        lambda name, **_: events.__setitem__(name, events.get(name, 0) + 1))
    log(f"[cache] compile cache {cache_dir}: {cache_entries(cache_dir)} "
        f"entries at start")
    log(f"[device] {devs[0].device_kind} x {len(devs)}")

    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    hits = events.get("/jax/compilation_cache/cache_hits", 0)
    misses = events.get("/jax/compilation_cache/cache_misses", 0)
    log(f"[cache] compile cache {cache_dir}: {cache_entries(cache_dir)} "
        f"entries at end, {hits} hits, {misses} misses this run")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
