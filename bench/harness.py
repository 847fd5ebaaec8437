"""One run of one benchmark cell, end to end.

The cell (`--workload`) names a configuration and a traffic mix in
`BENCHMARK.json`; each is a data file found by its name
(`configs/<config>.json`, `traffic/<mix>.json`), the traffic's `loop` names
its load driver (`drivers/<loop>.py`), and every metric is a reader of its
own (`metrics/<name>.py`).  A run:

1. refuses to run without a TPU (or with fewer chips than the cell asks);
2. turns on JAX's persistent compilation cache at a fixed path;
3. makes the points and the query pool on the device from the seed, and the
   exact projection (`data.py`);
4. builds the index with `ActiveSearcher.build` on the configuration's
   plan (the `pallas` backend);
5. warms exactly the batch shapes the traffic uses, through the queue;
6. drops its own copy of the points;
7. measures for `seconds` through `launch/serve.DynamicBatcher`;
8. reads the device's peak memory;
9. frees the program's state, regenerates the points and runs the plain
   reference (`reference.py`) over a sample of the served answers, then
   prints the metrics and the compared numbers.

With `trace=1` the window (cut to the traffic's `trace_seconds`) runs under
the profiler and the per-layer metrics are printed instead of the
end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


class RunError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """A module from a file whose name may hold dots (metric names do)."""
    if not path.is_file():
        raise RunError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of BENCHMARK.json with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    metrics: dict  # name -> (spec entry, reader module), this run's kind

    @classmethod
    def load(cls, root: Path, name: str, trace: bool) -> "Cell":
        spec = load_json(root / "BENCHMARK.json")
        wl = {w["name"]: w for w in spec["workloads"]}.get(name)
        if wl is None:
            raise RunError(f"no workload {name!r} in BENCHMARK.json")
        conf_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
        bench = root / spec["paths"][0]
        config = load_json(root / conf_entry["file"])
        traffic = load_json(bench / "traffic" / f"{wl['traffic']}.json")
        driver = load_module(bench / "drivers" / f"{traffic['loop']}.py")
        kind = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in spec[kind]:
            if name in m.get("workloads", [name]):
                metrics[m["name"]] = (
                    m, load_module(bench / "metrics" / f"{m['name']}.py"))
        return cls(name, wl["chips"], config, traffic, driver, metrics)


class CompileCounter:
    """Counts compilations (backend compiles and persistent-cache loads)."""

    EVENTS = ("/jax/compilation_cache/cache_hits",)
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.n += 1

    def _duration(self, name, _secs, **_):
        if name in self.DURATIONS:
            self.n += 1


def enable_compile_cache(root: Path) -> str:
    """JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_device(cell: Cell, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunError(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise RunError(f"the cell needs {cell.chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


def peaks_for(kind: str, require_tpu: bool) -> dict | None:
    table = load_json(BENCH / "peaks.json")
    if kind in table:
        return table[kind]
    if require_tpu:
        raise RunError(f"no peaks for device kind {kind!r} in peaks.json")
    return None


def build(cell: Cell, seed: int):
    """Data, projection, index, queue: everything up to the window."""
    import jax

    import data
    from repro import api
    from repro.core.grid import GridConfig
    from repro.launch.serve import DynamicBatcher

    conf = cell.config
    points, queries = data.make_data(conf, seed)
    proj = data.fixed_point_projection(points, queries, conf["value_max"])
    searcher = api.ActiveSearcher.build(
        points, cfg=GridConfig(**conf["grid"]),
        plan=api.ExecutionPlan(**conf["plan"]),
        proj=data.program_projection(proj))
    jax.block_until_ready(searcher.index)
    pool = np.asarray(queries)
    del points, queries
    batcher = DynamicBatcher(searcher, k=conf["k"],
                             max_batch=cell.traffic["max_batch"])
    cell.driver.warm(batcher, pool, cell.traffic)
    return batcher, pool, proj


def window(cell: Cell, batcher, pool, seconds: float, seed: int,
           trace_dir: str | None, counter: CompileCounter):
    """The measured window; returns the load driver's record."""
    import jax

    rng = np.random.default_rng([seed, 1])
    before = counter.n
    counts = {k: v for k, v in batcher.stats.items() if isinstance(v, int)}
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        rec = cell.driver.run(batcher, pool, cell.traffic, seconds, rng)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    rec["compiles_in_window"] = counter.n - before
    # the queue's counters over the window alone (warm-up left out)
    rec["batcher"] = {k: batcher.stats[k] - v for k, v in counts.items()}
    return rec


def reference_phase(cell: Cell, seed: int, proj: dict, pool: np.ndarray,
                    rec: dict, needs: set, controls: tuple = ()):
    """Regenerate the points, rebuild the grid in numpy, and compare a
    sample of the served answers with the reference.  Returns the compared
    numbers and fills what the metric readers need into `rec`.  Each
    precision in `controls` also puts the reference, computed in it, in the
    program's place: its numbers go to rec["controls"]."""
    import data
    import reference as ref_lib

    conf = cell.config
    points, queries = data.make_data(conf, seed)
    grid_size = conf["grid"]["grid_size"]
    grid = ref_lib.Grid(ref_lib.grid_coords(ref_lib.project(points, proj),
                                            proj, grid_size), conf["grid"])
    pool_coords = ref_lib.grid_coords(ref_lib.project(queries, proj), proj,
                                      grid_size)

    served = rec["served"]
    n_rows = len(rec["pool_rows"])
    rng = np.random.default_rng([seed, 2])
    pick = np.sort(rng.choice(n_rows, size=min(n_rows, conf["check_sample"]),
                              replace=False))
    rows = rec["pool_rows"][pick]
    sample = {f: v[pick] for f, v in served.items()}
    ref = ref_lib.search(grid, points, pool[rows], pool_coords[rows],
                         conf["k"])
    true = ref_lib.true_dists(points, pool[rows], sample["ids"])
    nums = ref_lib.compare(sample, ref, true)
    rec["controls"] = {}
    for precision in controls:
        ctl = ref_lib.search(grid, points, pool[rows], pool_coords[rows],
                             conf["k"], precision)
        rec["controls"][precision] = ref_lib.compare(
            ctl, ref, ref_lib.true_dists(points, pool[rows], ctl["ids"]))
    # share of the sample left out of loop_mismatch by an open rounding
    rec["open_share"] = float(np.mean(ref["open"]))
    if "ground_truth" in needs:
        used = np.unique(rec["pool_rows"])
        gt = np.full((pool.shape[0], conf["k"]), -1, np.int64)
        gt[used] = ref_lib.exact_knn(points, pool[used], conf["k"])
        rec["ground_truth"] = gt
    if "candidate_bytes" in needs:
        per_query = ref_lib.window_bytes(grid, pool_coords, conf["dim"])
        rec["candidate_bytes"] = float(per_query[rec["pool_rows"]].sum())
    del points, queries
    return nums


def run(root: Path, workload: str, seed: int, seconds: int, trace: bool,
        t_start: float, require_tpu: bool = True) -> dict:
    """One run; returns the result line as a dict (see run.py)."""
    cell = Cell.load(root, workload, trace)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    devs = check_device(cell, require_tpu)
    dev = devs[0]
    peaks = peaks_for(dev.device_kind, require_tpu)
    cache = enable_compile_cache(root)
    counter = CompileCounter()
    log(f"[bench] {workload} seed {seed} on {dev.device_kind} x {len(devs)}; "
        f"compile cache {cache}")

    batcher, pool, proj = build(cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"[bench] set-up {setup_s!r} s, {counter.n} compilations")

    win_s = min(seconds, cell.traffic["trace_seconds"]) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        rec = window(cell, batcher, pool, win_s, seed, trace_dir, counter)
        stats = dev.memory_stats() or {}
        rec["peak_bytes"] = stats.get("peak_bytes_in_use")
        rec["trace"] = None
        if trace_dir:
            import trace_reduce

            rec["trace"] = trace_reduce.reduce(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
            if rec["trace"] is None:
                raise RunError("the trace holds no window span, or no "
                               "device operation inside it")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[bench] window {rec['t1'] - rec['t0']!r} s, {rec['attempted']} "
        f"requests, {len(rec['pool_rows'])} queries, "
        f"{rec['compiles_in_window']} compilations in the window")
    if "generator_late_s" in rec:
        late = rec["generator_late_s"]
        log(f"[bench] generator lateness: median {float(np.median(late))!r} "
            f"s, p99 {float(np.percentile(late, 99))!r} s")

    n_points = cell.config["n_points"]
    del batcher
    gc.collect()

    needs = set()
    for _, mod in cell.metrics.values():
        needs.update(getattr(mod, "NEEDS", ()))
    nums = reference_phase(cell, seed, proj, pool, rec, needs)

    rec.update(setup_s=setup_s, n_points=n_points, peaks=peaks)
    metrics = {}
    for name, (entry, mod) in cell.metrics.items():
        value = mod.read(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}

    limits = cell.config["limits"]
    checks = {n: {"value": nums[n], "limit": limits[n]} for n in limits}
    failed = int(rec["failed"])
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": rec["peak_bytes"]}
    out = {"correct": correct, "attempted": int(rec["attempted"]),
           "failed": failed, "metrics": metrics, "device": device}
    if rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["top_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    log(f"[bench] loop comparison left out {rec['open_share']!r} of the "
        f"sample (float32 rounding left open)")
    for n, c in checks.items():
        log(f"check {n} {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out
