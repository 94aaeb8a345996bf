"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

Device planes are those named `/device:TPU:<n>`.  On each, the line
`XLA Ops` holds one event per operation that ran and `XLA Modules` one
per program execution, named after the jitted function (`jit_decode`,
`jit_prefill`, ...).  Busy time is the union of the operation intervals
inside the traced window; the window is the host span `WINDOW_SPAN`,
which the harness opens and closes around the measured traffic.  Each
idle gap of the device is named after the innermost harness span that
covers its midpoint on the host.
"""
from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # mean over the device planes
    devices: int
    modules: dict                   # program name -> (calls, device s)
    top_ops: list                   # [[op name, self device s], ...]
    idle_gaps: list                 # [[host span, gap s], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """`%wkv4_pallas.7 = (f32[...]) custom-call(...)` -> `wkv4_pallas`."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def self_times(events):
    """{op name: seconds} with each event's nested children taken out
    (a `while` op holds the operations of its body on the same line)."""
    out = collections.Counter()
    stack = []          # [end, name, self ns] of the open events
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            _, done, ns = stack.pop()
            out[done] += ns * 1e-9
        if stack:
            stack[-1][2] -= end - start
        stack.append([end, name, end - start])
    for _, name, ns in stack:
        out[name] += ns * 1e-9
    return out


def module_name(event_name: str) -> str:
    """`jit_decode(12)` or `jit_decode` -> `decode`."""
    name = event_name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def summarize(profile, host_spans=(), n_top: int = 10) -> Summary:
    """Reduce a `jax.profiler.ProfileData`.  `host_spans` are the span
    names that may label an idle gap."""
    planes = list(profile.planes)
    window = None
    labels = []
    for plane in planes:
        if _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in host_spans:
                    labels.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    devices = [p for p in planes if _DEVICE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0.0
    modules = collections.defaultdict(lambda: [0, 0.0])
    ops = collections.Counter()     # op name -> self seconds
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        spans = _clip([(ev.start_ns, ev.end_ns, op_name(ev.name))
                       for ev in (op_line.events if op_line else ())],
                      lo, hi)
        ops.update(self_times(spans))
        busy = _union([(s, e) for s, e, _ in spans])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for ev in (lines[MODULES_LINE].events
                   if MODULES_LINE in lines else ()):
            if lo <= ev.start_ns < hi:
                m = modules[module_name(ev.name)]
                m[0] += 1
                m[1] += ev.duration_ns * 1e-9
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:n_top]:
        mid = (s + e) / 2
        covering = [(le - ls, name) for ls, le, name in labels
                    if ls <= mid <= le]
        named.append([min(covering)[1] if covering else "outside spans",
                      (e - s) * 1e-9])
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(devices),
        devices=len(devices),
        modules={k: (v[0], v[1]) for k, v in modules.items()},
        top_ops=[[k, v] for k, v in ops.most_common(n_top)],
        idle_gaps=named)
