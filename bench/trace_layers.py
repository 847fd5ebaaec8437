"""Device time by layer of the program, and device idle time by what the
host was doing, from a profiler trace (`.xplane.pb`) of a benchmark window.

The search programs name their stages with `jax.named_scope`
(`search.project`, `search.radius_loop`, `search.window`,
`search.candidates`, `search.records`, in `core/batched.py`).  A scope lives
in each op's HLO metadata (`op_name="jit(_search_impl)/search.radius_loop/
while/body/..."`), which the optimized HLO text of the executable
(`Compiled.as_text()`) holds under the same instruction names a device
trace gives its ops.  So an op's layer is found by (HLO module, op name):

- the first `search.*` scope on the op's `op_name` path;
- an op of a search program with no scope of its own (a layout copy the
  compiler puts in front of a loop carries no metadata) takes the layer of
  the op that uses its result: the work belongs to the stage that asks
  for it; failing that (a layout copy of a program output), the layer of
  the op whose result it takes;
- ops of executables that hold no scope at all (the eager slices,
  concatenations and puts the serving queue dispatches) are `queue`;
- what is left is `unscoped`.

`layer_seconds` takes, per layer, the union of its ops' intervals, so that
a `while` envelope and the ops of its body count once; clipped to the
window and averaged over the devices.  `idle_by_span` gives the device's
idle time inside the window by the innermost program or client span open
at each gap's midpoint (`queue.*` and `search.*` spans of the program,
`submit`, `step.*` and `result` of the benchmark's client loop,
`serving.py`); Python-tracer frames (`$...`) and JAX's own events are
passed over, and a gap under none of these spans counts as `none`.
"""

from __future__ import annotations

import bisect
import collections
import re

SCOPE_PREFIX = "search."
QUEUE = "queue"
UNSCOPED = "unscoped"
NONE = "none"
MODULES_LINE = "XLA Modules"
# the benchmark client's spans (serving.py); `window` holds them all
CLIENT_SPANS = ("submit", "step.search", "step.insert", "result")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The first `search.*` component of an op_name path."""
    for part in op_name.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def hlo_layers(text: str) -> dict[str, str]:
    """Instruction name -> layer for one executable's optimized HLO text.
    Empty where the executable holds no `search.*` scope (its ops are then
    `queue`)."""
    layer: dict[str, str | None] = {}
    operands: dict[str, list[str]] = {}
    users: dict[str, list[str]] = collections.defaultdict(list)
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        md = _OP_NAME.search(rhs)
        layer[name] = scope_of(md.group(1)) if md else None
        operands[name] = _OPERAND.findall(rhs.split("metadata=", 1)[0])
        for operand in operands[name]:
            users[operand].append(name)
    if not any(layer.values()):
        return {}
    # an unscoped op takes the layer of its first scoped user, transitively;
    # one that feeds only unscoped ops (a layout copy of a result) that of
    # its first scoped operand
    for neighbours in (users, operands):
        changed = True
        while changed:
            changed = False
            for name, lay in layer.items():
                if lay is None:
                    for other in neighbours.get(name, ()):
                        if layer.get(other):
                            layer[name] = layer[other]
                            changed = True
                            break
    return {n: lay or UNSCOPED for n, lay in layer.items()}


def module_layers(texts) -> dict[str, dict[str, str]]:
    """HLO module name -> `hlo_layers`, for the optimized HLO texts of the
    executables a window ran.  Executables of one module name (one jitted
    function at several shapes) share one table."""
    out: dict[str, dict[str, str]] = {}
    for text in texts:
        m = re.match(r"\s*HloModule\s+([\w.\-]+)", text)
        if m:
            out.setdefault(m.group(1), {}).update(hlo_layers(text))
    return out


def layer_of(module: str, op: str, layers: dict[str, dict[str, str]]) -> str:
    """An op's layer from its module's `hlo_layers` map; a module that holds
    no scope (or was not compiled by the search programs) is `queue`."""
    table = layers.get(module)
    if not table:
        return QUEUE
    return table.get(op, UNSCOPED)


def _module_name(name: str) -> str:
    """`jit__search_impl(12)` -> `jit__search_impl`."""
    return name.split("(", 1)[0].strip()


def load(path) -> dict:
    """The window thread's host spans and, per device, its ops as
    (HLO module, op name, start_ns, end_ns).  A TPU's `XLA Ops` events
    carry no module; an op's module is the `XLA Modules` event (one run of
    an executable, `jit__search_impl(<fingerprint>)`) it runs inside."""
    from jax.profiler import ProfileData

    from trace_reduce import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE,
                              host_spans, short_name)

    pd = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            spans = host_spans(
                [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in line.events] for line in plane.lines)
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _module_name(ev.name))
                          for ev in lines.get(MODULES_LINE, ()))
            starts = [r[0] for r in runs]
            ops = []
            for ev in lines.get(OPS_LINE, ()):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                module = (runs[i][2] if i >= 0 and ev.start_ns < runs[i][1]
                          else "")
                ops.append((module, short_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns))
            devices[plane.name] = ops
    return {"spans": spans, "devices": devices}


def _union(intervals):
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clipped_length(intervals, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in _union(intervals))


def window_of(spans) -> tuple[float, float] | None:
    for name, s, e in spans:
        if name == "window":
            return s, e
    return None


def layer_seconds(raw: dict, layers: dict[str, dict[str, str]]) -> dict:
    """Device seconds per layer inside the window, each layer the union of
    its ops' intervals, averaged over the devices.  `raw["devices"]` maps a
    device to its ops as (module, op, start_ns, end_ns)."""
    w = window_of(raw["spans"])
    if w is None or not raw["devices"]:
        return {}
    per: dict[str, list] = collections.defaultdict(list)
    out: dict[str, float] = collections.defaultdict(float)
    for ops in raw["devices"].values():
        per.clear()
        for module, op, s, e in ops:
            if e > w[0] and s < w[1]:
                per[layer_of(module, op, layers)].append((s, e))
        for lay, iv in per.items():
            out[lay] += _clipped_length(iv, *w)
    n = len(raw["devices"])
    return {k: v / n * 1e-9 for k, v in sorted(out.items())}


def _attributable(name: str) -> bool:
    return name.startswith(("queue.", SCOPE_PREFIX)) or name in CLIENT_SPANS


def idle_by_span(raw: dict) -> dict:
    """Device idle seconds inside the window by the innermost program or
    client span open at each gap's midpoint (`none` where there is none),
    averaged over the devices."""
    w = window_of(raw["spans"])
    if w is None or not raw["devices"]:
        return {}
    # by start, and among spans that start together the outer one first
    spans = sorted((s for s in raw["spans"] if _attributable(s[0])),
                   key=lambda s: (s[1], -s[2]))
    starts = [s[1] for s in spans]
    out: dict[str, float] = collections.defaultdict(float)
    for ops in raw["devices"].values():
        busy = _union([(s, e) for _, _, s, e in ops if e > w[0] and s < w[1]])
        edges = [w[0]] + [min(max(x, w[0]), w[1]) for iv in busy
                          for x in iv] + [w[1]]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = NONE
            # innermost: the latest-starting span that holds the midpoint
            for name, s, e in reversed(spans[:bisect.bisect_right(starts,
                                                                  mid)]):
                if s <= mid < e:
                    label = name
                    break
            out[label] += g1 - g0
    n = len(raw["devices"])
    return {k: v / n * 1e-9 for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}
