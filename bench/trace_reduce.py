"""Reduce a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Device planes are `/device:TPU:<n>`; on each, the line `XLA Ops` holds one
event per operation run on the device.  Host spans are the benchmark's own
`jax.profiler.TraceAnnotation`s, on the host plane's line of the thread that
opened them, which is named after the process (`python3`, `python`, ...): the
line that holds the span named `window`, the traced window.

From these:

- `busy_s`: the union of the device-op intervals inside the window, averaged
  over the devices; `window_s`: the window's length;
- `op_seconds`: device seconds per operation label, where a label is the
  op's name followed by its `tf_op` path (the jit scopes it was traced
  under), so a metric can sum the ops under one jitted function;
- `top_ops`: the ten operation names that took most device time;
- `idle_gaps`: device idle time inside the window, by the innermost host
  span that was open at each gap's midpoint (`idle` where none was), the ten
  largest totals.
"""

from __future__ import annotations

import bisect
import collections
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


def find_xplane(trace_dir: str | Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """An op's HLO name: TPU traces name an op by its whole instruction text
    (`%fusion.3 = f32[...] fusion(...)`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(iv: list[list[float]], lo: float, hi: float) -> list[list[float]]:
    out = []
    for s, e in iv:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append([s, e])
    return out


def load(path: str | Path) -> dict:
    """The raw events: host spans and, per device, its ops."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            spans = host_spans(
                [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in line.events] for line in plane.lines)
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    tf_op = _stats(ev).get("tf_op", "")
                    ops.append((short_name(ev.name), str(tf_op), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
            devices[plane.name] = ops
    return {"spans": spans, "devices": devices}


def host_spans(lines) -> list[tuple[str, float, float]]:
    """The spans of the first host line (a thread) that holds the window
    span; none where no line does."""
    for events in lines:
        if any(name == WINDOW_SPAN for name, _, _ in events):
            return events
    return []


def reduce(raw: dict, top: int = 10) -> dict | None:
    """The numbers listed in the module docstring, or None when the trace
    holds no window span or no device op in it."""
    win = [s for s in raw["spans"] if s[0] == WINDOW_SPAN]
    if not win or not raw["devices"]:
        return None
    w0, w1 = win[0][1], win[0][2]
    spans = sorted((s for s in raw["spans"] if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    op_ns: dict[str, float] = collections.defaultdict(float)
    name_ns: dict[str, float] = collections.defaultdict(float)
    gap_ns: dict[str, float] = collections.defaultdict(float)
    busy_total = 0.0
    n_ops = 0
    for ops in raw["devices"].values():
        inside = [o for o in ops if o[3] > w0 and o[2] < w1]
        n_ops += len(inside)
        for name, tf_op, s, e in inside:
            d = min(e, w1) - max(s, w0)
            op_ns[f"{name} {tf_op}"] += d
            name_ns[name] += d
        busy = _clip(_union([(o[2], o[3]) for o in inside]), w0, w1)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = "idle"
            # innermost open span: the latest-starting one that holds mid
            i = bisect.bisect_right(starts, mid)
            for name, s, e in reversed(spans[max(0, i - 64):i]):
                if s <= mid < e:
                    label = name
                    break
            gap_ns[label] += g1 - g0
    if n_ops == 0:
        return None
    n_dev = len(raw["devices"])
    ns = 1e-9
    return {
        "busy_s": busy_total / n_dev * ns,
        "window_s": (w1 - w0) * ns,
        "op_seconds": {k: v / n_dev * ns for k, v in op_ns.items()},
        "top_ops": [[k, v / n_dev * ns] for k, v in
                    sorted(name_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n_dev * ns] for k, v in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]],
    }


def scope_seconds(trace: dict, scope: str) -> float:
    """Device seconds of the ops traced under a jit scope named `scope`."""
    return sum(v for k, v in trace["op_seconds"].items() if scope in k)
