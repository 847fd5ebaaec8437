"""The trace reduction, on hand-made events laid out as a TPU trace lays
them out (exact sums)."""

from __future__ import annotations

import pytest

import trace_reduce


def test_union_idle_and_gaps_by_hand():
    raw = {
        "spans": [("window", 0, 100), ("step.search", 10, 60),
                  ("result", 60, 70), ("submit", 80, 90)],
        "devices": {"/device:TPU:0": [
            ("fusion.1", "jit(f)/jit(tile_count_multilevel)/pallas_call",
             10, 30),
            ("fusion.1", "jit(f)/jit(tile_count_multilevel)/pallas_call",
             20, 40),   # overlaps the first: the union counts 10..40 once
            ("custom-call.2", "jit(f)/jit(csr_candidate_topk)/pallas_call",
             50, 55),
            ("copy.3", "", 95, 120),  # clipped at the window's end
        ]},
    }
    tr = trace_reduce.reduce(raw)
    ns = 1e-9
    assert tr["window_s"] == pytest.approx(100 * ns)
    assert tr["busy_s"] == pytest.approx((30 + 5 + 5) * ns)
    assert trace_reduce.scope_seconds(tr, "tile_count_multilevel") == \
        pytest.approx(40 * ns)
    assert trace_reduce.scope_seconds(tr, "csr_candidate_topk") == \
        pytest.approx(5 * ns)
    gaps = dict(tr["idle_gaps"])
    # gaps: 0-10 idle, 40-50 step, 55-95 midpoint 75 -> no span open
    assert gaps["idle"] == pytest.approx((10 + 40) * ns)
    assert gaps["step.search"] == pytest.approx(10 * ns)


def test_no_window_or_no_device_gives_nothing():
    assert trace_reduce.reduce({"spans": [], "devices": {}}) is None
    assert trace_reduce.reduce(
        {"spans": [("window", 0, 10)], "devices": {}}) is None


def test_tpu_op_names_are_shortened():
    """A TPU trace names an op by its whole HLO instruction, which holds its
    operands' names too; only the op's own name is kept."""
    text = ("%fusion.7 = f32[256,10]{1,0} fusion(s32[256,1,1]{2,1,0} "
            "%tile_count_multilevel.10), kind=kLoop")
    assert trace_reduce.short_name(text) == "fusion.7"
    assert trace_reduce.short_name("copy.3") == "copy.3"


def test_host_spans_from_the_window_thread_whatever_its_name():
    """The host line is named after the process (`python3` when run so);
    spans come from the line that holds the window, and other threads'
    events are left out."""
    runtime = [("ThunkExecute", 5, 6)]
    main = [("submit", 1, 2), ("window", 0, 10)]
    assert trace_reduce.host_spans([runtime, main]) == main
    assert trace_reduce.host_spans([runtime]) == []


def test_load_finds_the_window_in_a_real_trace(tmp_path):
    """A trace recorded here (on the CPU: host spans, no TPU plane) is read
    back with its window and inner spans."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("window"):
        with TraceAnnotation("step.search"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = trace_reduce.load(trace_reduce.find_xplane(tmp_path))
    names = [s[0] for s in raw["spans"]]
    assert "window" in names and "step.search" in names


def _chip_slice():
    """40 ms of a `sift1m.batch` trace recorded on a TPU v5 lite: the host
    spans of the window's thread (the Python tracer's spans under 50 us left
    out) and the device's `XLA Ops`, cut to a window of their own."""
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "sift1m_batch_trace.json"
    raw = json.loads(path.read_text())
    raw["spans"] = [tuple(s) for s in raw["spans"]]
    raw["devices"] = {k: [tuple(o) for o in v]
                      for k, v in raw["devices"].items()}
    return raw


def test_reduce_on_a_chip_trace():
    """Busy time by a sweep over the interval ends, and per-kernel time by a
    plain sum of clipped durations, agree with the reduction."""
    raw = _chip_slice()
    (w0, w1), = [(s, e) for n, s, e in raw["spans"] if n == "window"]
    ops = raw["devices"]["/device:TPU:0"]
    tr = trace_reduce.reduce(raw)
    ns = 1e-9

    ends = sorted([(max(s, w0), 1) for _, _, s, e in ops if e > w0 and s < w1]
                  + [(min(e, w1), -1) for _, _, s, e in ops
                     if e > w0 and s < w1])
    busy, depth, since = 0.0, 0, None
    for t, step in ends:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    assert tr["window_s"] == pytest.approx((w1 - w0) * ns)
    assert tr["busy_s"] == pytest.approx(busy * ns)
    assert 0 < tr["busy_s"] < tr["window_s"]
    idle = sum(v for _, v in tr["idle_gaps"])
    assert idle <= tr["window_s"] - tr["busy_s"] + 1e-12

    # the two kernels are told apart by their ops' names on the chip
    for scope in ("tile_count_multilevel", "csr_candidate_topk"):
        want = sum(min(e, w1) - max(s, w0) for name, tf_op, s, e in ops
                   if scope in f"{name} {tf_op}" and e > w0 and s < w1)
        assert want > 0
        assert trace_reduce.scope_seconds(tr, scope) == \
            pytest.approx(want * ns)
    assert not any(" = " in name for name, _ in tr["top_ops"])
