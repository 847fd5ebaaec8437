"""Device time by program layer and idle time by host span
(`trace_layers.py`): the HLO scope map, and the interval sums on
hand-made events."""

from __future__ import annotations

import pytest

import trace_layers as tl

HLO = """HloModule jit__search_impl, is_scheduled=true

%body (p: (s32[], s32[4])) -> (s32[], s32[4]) {
  %p = (s32[], s32[4]) parameter(0)
  %fusion.2 = s32[4] fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(_search_impl)/search.radius_loop/while/body/jit(tile_count_multilevel)/add" stack_frame_id=3}
  ROOT %tuple.1 = (s32[], s32[4]) tuple(%p, %fusion.2)
}

ENTRY %main (a: f32[4,2], t: s32[8,3]) -> s32[4] {
  %a = f32[4,2] parameter(0), metadata={op_name="queries"}
  %t = s32[8,3] parameter(1), metadata={op_name="index.pyr_tiles"}
  %fusion.1 = f32[4] fusion(%a), kind=kLoop, calls=%fp, metadata={op_name="jit(_search_impl)/search.project/dot_general"}
  %copy.5 = s32[8,3]{1,0:T(8,128)} copy(%t)
  %while.4 = (s32[], s32[4]) while(%fusion.1, %copy.5), condition=%cond, body=%body, metadata={op_name="jit(_search_impl)/search.radius_loop/while" stack_frame_id=4}
  %gte.1 = s32[4] get-tuple-element(%while.4), index=1
  %csr_candidate_topk.1 = s32[4] custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_search_impl)/search.candidates/jit(csr_candidate_topk)/pallas_call"}
  %copy.9 = s32[4] copy(%csr_candidate_topk.1)
  ROOT %fusion.3 = s32[4] fusion(%copy.9), kind=kLoop, calls=%fr, metadata={op_name="jit(_search_impl)/search.records/select_n"}
}
"""

EAGER = """HloModule jit_dynamic_slice, is_scheduled=true

ENTRY %main (a: f32[8]) -> f32[1] {
  %a = f32[8] parameter(0), metadata={op_name="a"}
  ROOT %dynamic-slice.1 = f32[1] dynamic-slice(%a), metadata={op_name="dynamic_slice"}
}
"""


def test_scope_map_from_optimized_hlo():
    layers = tl.module_layers([HLO, EAGER])
    t = layers["jit__search_impl"]
    assert t["fusion.1"] == "search.project"
    assert t["while.4"] == t["fusion.2"] == "search.radius_loop"
    assert t["csr_candidate_topk.1"] == "search.candidates"
    assert t["fusion.3"] == "search.records"
    # no metadata: the layer of the op that uses the result
    assert t["copy.5"] == "search.radius_loop"
    assert t["copy.9"] == "search.records"
    assert t["gte.1"] == "search.candidates"
    # an executable without any scope is the queue's
    assert layers["jit_dynamic_slice"] == {}
    assert tl.layer_of("jit_dynamic_slice", "dynamic-slice.1",
                       layers) == "queue"
    assert tl.layer_of("jit__search_impl", "fusion.99", layers) == \
        "unscoped"


def test_scope_map_of_a_compiled_search_program():
    """The CPU-compiled pallas search program maps its ops to the five
    stages; its `while`s (the radius loop's, and the interpreted kernels'
    grid loops) all have a stage."""
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core import batched
    from repro.core.grid import GridConfig, build_index
    from repro.core.projection import identity_projection

    cfg = GridConfig(grid_size=64, tile=8, window=16, row_cap=8, r0=4)
    pts = jnp.asarray(np.random.default_rng(0).normal(size=(256, 2)),
                      jnp.float32)
    s = api.ActiveSearcher.from_index(
        build_index(pts, cfg, identity_projection(pts)), cfg)
    text = batched._search_impl.lower(
        s.index, s.cfg, jnp.zeros((8, 2), jnp.float32), 4, "refined", None,
        batched.get_candidate_pipeline("fused"), None, False,
    ).compile().as_text()
    (name, table), = tl.module_layers([text]).items()
    assert name == "jit__search_impl"
    found = set(table.values())
    assert {"search.project", "search.radius_loop", "search.window",
            "search.candidates", "search.records"} <= found
    whiles = [n for n in table if n.startswith("while")]
    assert "search.radius_loop" in {table[n] for n in whiles}
    assert "unscoped" not in {table[n] for n in whiles}


def _raw():
    # one device; window 0..100; spans of the client and the program, with
    # a Python-tracer frame and a JAX event that are passed over
    spans = [("window", 0, 100), ("step.search", 5, 70),
             ("queue.batch", 5, 70), ("queue.assemble", 5, 15),
             ("queue.dispatch", 15, 30), ("$serve.py:1 _run_batch", 5, 70),
             ("queue.sync", 30, 60), ("queue.resolve", 60, 70),
             ("PjitFunction(dynamic_slice)", 61, 69), ("result", 75, 85)]
    ops = [("jit__search_impl", "fusion.1", 10, 20),     # project
           ("jit__search_impl", "while.4", 20, 40),      # envelope ...
           ("jit__search_impl", "fusion.2", 22, 30),     # ... and its body
           ("jit__search_impl", "fusion.2", 32, 38),
           ("jit__search_impl", "copy.5", 40, 44),       # asked by the loop
           ("jit__search_impl", "csr_candidate_topk.1", 44, 58),
           ("jit__search_impl", "fusion.77", 58, 59),    # not in the map
           ("jit_dynamic_slice", "dynamic-slice.1", 62, 63),
           ("jit__search_impl", "fusion.3", 95, 110)]    # clipped at 100
    return {"spans": spans, "devices": {"/device:TPU:0": ops}}


def test_layer_seconds_by_hand():
    layers = tl.module_layers([HLO, EAGER])
    got = tl.layer_seconds(_raw(), layers)
    ns = 1e-9
    assert got == pytest.approx({
        "search.project": 10 * ns,
        "search.radius_loop": (20 + 4) * ns,   # envelope and body once
        "search.candidates": 14 * ns,
        "search.records": 5 * ns,
        "unscoped": 1 * ns,
        "queue": 1 * ns,
    })


def test_idle_by_span_by_hand():
    got = tl.idle_by_span(_raw())
    ns = 1e-9
    # busy 10..59, 62..63, 95..100; gaps 0..10 (mid 5: assemble),
    # 59..62 (mid 60.5: resolve), 63..95 (mid 79: result)
    assert got == pytest.approx({"queue.assemble": 10 * ns,
                                 "queue.resolve": 3 * ns,
                                 "result": 32 * ns})
    raw = _raw()
    raw["spans"] = [s for s in raw["spans"] if s[0] != "result"]
    assert tl.idle_by_span(raw)["none"] == pytest.approx(32 * ns)


def test_no_window_gives_nothing():
    raw = _raw()
    raw["spans"] = raw["spans"][1:]
    assert tl.layer_seconds(raw, {}) == {}
    assert tl.idle_by_span(raw) == {}


def _chip_slice():
    """24 ms of a `sift1m.batch` trace recorded on a TPU v5 lite with the
    program's spans and scopes: the end of one batch, the host's work
    between batches and the start of the next.  Host spans of the window's
    thread (Python-tracer frames under 200 us left out), the device's ops
    as (module, op, start, end), with the module read off the device's
    `XLA Modules` line, and the scope map of the search program's ops
    (`hlo_layers` of its compiled text), cut to a window of their own."""
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "sift1m_batch_scoped_trace.json"
    raw = json.loads(path.read_text())
    layers = raw.pop("layers")
    raw["spans"] = [tuple(s) for s in raw["spans"]]
    raw["devices"] = {k: [tuple(o) for o in v]
                      for k, v in raw["devices"].items()}
    return raw, layers


def _sweep(intervals, lo, hi):
    """Covered length by a sweep over the interval ends (clipped; an
    interval of no length covers nothing)."""
    clipped = [(max(s, lo), min(e, hi)) for s, e in intervals]
    clipped = [(s, e) for s, e in clipped if e > s]
    ends = sorted([(s, 1) for s, _ in clipped] + [(e, -1) for _, e in clipped])
    covered, depth, since = 0.0, 0, None
    for t, step in ends:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            covered += t - since
    return covered


def test_layers_on_a_chip_trace():
    """Per-layer device time by a sweep over each layer's ops agrees with
    the reduction; the layers tile the busy time that `trace_reduce`
    reports, and the search program leaves nothing unscoped."""
    import trace_reduce

    raw, layers = _chip_slice()
    (w0, w1), = [(s, e) for n, s, e in raw["spans"] if n == "window"]
    ops = raw["devices"]["/device:TPU:0"]
    got = tl.layer_seconds(raw, layers)
    ns = 1e-9
    by_layer = {}
    for module, op, s, e in ops:
        lay = (layers[module].get(op, "unscoped") if module in layers
               else "queue")
        by_layer.setdefault(lay, []).append((s, e))
    want = {k: _sweep(v, w0, w1) * ns for k, v in by_layer.items()}
    assert got == pytest.approx(want)
    assert {"search.radius_loop", "search.candidates", "queue"} <= set(got)
    assert "unscoped" not in got

    busy = trace_reduce.reduce({
        "spans": raw["spans"],
        "devices": {"/device:TPU:0": [(op, "", s, e)
                                      for _, op, s, e in ops]}})["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_idle_by_span_on_a_chip_trace():
    """Idle time by span from an independent walk over the gaps (the
    innermost span: the latest-starting one holding the gap's midpoint,
    among the program's and the client's spans) agrees with the
    reduction, and covers the window's idle time."""
    raw, _ = _chip_slice()
    (w0, w1), = [(s, e) for n, s, e in raw["spans"] if n == "window"]
    ops = raw["devices"]["/device:TPU:0"]
    iv = sorted((max(s, w0), min(e, w1)) for _, _, s, e in ops
                if e > w0 and s < w1)
    gaps, t = [], w0
    for s, e in iv:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    ours = [s for s in raw["spans"] if s[0].startswith(("queue.", "search."))
            or s[0] in ("submit", "step.search", "step.insert", "result")]
    want = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        holding = [s for s in ours if s[1] <= mid < s[2]]
        # among spans that start together, the shorter is the inner one
        label = (max(holding, key=lambda s: (s[1], -s[2]))[0] if holding
                 else "none")
        want[label] = want.get(label, 0.0) + (g1 - g0) * 1e-9
    got = tl.idle_by_span(raw)
    assert got == pytest.approx(want)
    assert "queue.sync" in got and "none" not in got
    idle = (w1 - w0) * 1e-9 - _sweep([(s, e) for _, _, s, e in ops],
                                     w0, w1) * 1e-9
    assert sum(got.values()) == pytest.approx(idle)


def test_layer_split_runs_both_windows_and_maps_the_program(tiny_root,
                                                             monkeypatch):
    """On the CPU the script builds the cell, runs the untraced and the
    traced window, and stops where the trace holds no device (a TPU trace
    is what it reduces); the search program it compiles for the scope map
    is the one the queue ran, with all five stages."""
    import harness
    import layer_split

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    with pytest.raises(harness.RunError, match="no device operation"):
        layer_split.split(tiny_root, "sift1m.online", 3, 1,
                          require_tpu=False)

    cell = harness.Cell.load(tiny_root, "sift1m.online", trace=False)
    batcher, _, _ = harness.build(cell, 3)
    texts = layer_split.search_program_texts(batcher.searcher, cell)
    assert len(texts) == cell.traffic["max_batch"].bit_length()  # 1, 2, 4
    (name, table), = tl.module_layers(texts).items()
    assert name == "jit__search_impl"
    assert {"search.project", "search.radius_loop", "search.window",
            "search.candidates", "search.records"} <= set(table.values())
