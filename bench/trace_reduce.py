"""Reduce a JAX profiler trace (`.xplane.pb`) to what the per-layer metrics
read.

`load` keeps, from the planes of the trace:
  * per device (`/device:TPU:<n>`): the program executions of the
    "XLA Modules" line and the operations of the "XLA Ops" line, as
    (name, start_ns, end_ns);
  * from the host planes: every event of every thread, as
    (name, start_ns, end_ns, stats), which holds the benchmark's own
    `TraceAnnotation`s ("bench_window" around the traced slice,
    "bench_step" with its `idx` around each engine step) on the clock the
    device events use.

The reductions are plain functions of that structure: the union of the
device's busy intervals inside the window, the device time of each step's
program (its module executions inside the step's host span), the time of
collective operations, and the `breakdown` of the result line.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

Span = Tuple[str, int, int]


@dataclasses.dataclass
class Trace:
    modules: Dict[int, List[Span]]
    ops: Dict[int, List[Span]]
    host: List[Tuple[str, int, int, Dict]]

    @property
    def devices(self) -> List[int]:
        return sorted(set(self.modules) | set(self.ops))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    modules: Dict[int, List[Span]] = defaultdict(list)
    ops: Dict[int, List[Span]] = defaultdict(list)
    host: List[Tuple[str, int, int, Dict]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dest[dev].append((e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    host.append((e.name, s, s + int(e.duration_ns),
                                 dict(e.stats)))
    for d in (modules, ops):
        for v in d.values():
            v.sort(key=lambda x: x[1])
    host.sort(key=lambda x: x[1])
    return Trace(dict(modules), dict(ops), host)


def window(trace: Trace) -> Tuple[int, int]:
    """The traced slice: the span of the "bench_window" annotation."""
    for name, s, e, _ in trace.host:
        if name == "bench_window":
            return s, e
    raise ValueError("trace holds no bench_window annotation")


def step_spans(trace: Trace) -> List[Tuple[int, int, int]]:
    """(idx, start_ns, end_ns) of every "bench_step" annotation."""
    return [(int(st["idx"]), s, e) for name, s, e, st in trace.host
            if name == "bench_step"]


def union_ns(spans: List[Span], lo: int, hi: int) -> int:
    """Length of the union of the spans, clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for _, s, e in sorted(spans, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, inside the window, averaged over
    the devices."""
    lo, hi = window(trace)
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(union_ns(trace.ops.get(d, []), lo, hi) for d in devs) \
        / len(devs) / 1e9


def window_s(trace: Trace) -> float:
    lo, hi = window(trace)
    return (hi - lo) / 1e9


def module_name(name: str) -> str:
    """'jit_unified_step(123)' -> 'jit_unified_step'."""
    return name.split("(", 1)[0]


def step_device_s(trace: Trace,
                  expected: Dict[int, str]) -> Dict[int, Tuple[str, float]]:
    """Per traced engine step: (program, device seconds), averaged over the
    devices.

    `expected` maps a step's `idx` to the module its step ran (one per
    step).  Device and host clocks of a trace are not aligned to the
    microsecond, so steps are paired with module executions by order: on
    each device, the executions of the expected modules in time order
    against the traced steps in time order, at the shift (of at most three
    — a step that began before the trace, or ended after it) under which
    the most names agree."""
    names = set(expected.values())
    host = [(idx, s) for idx, s, _ in step_spans(trace) if idx in expected]
    host.sort(key=lambda x: x[1])
    per_step: Dict[int, List[float]] = defaultdict(list)
    for d in trace.devices:
        mods = [(module_name(n), s, e) for n, s, e in trace.modules.get(d, [])
                if module_name(n) in names]

        def agree(k):
            return sum(1 for i, (idx, _) in enumerate(host)
                       if 0 <= i + k < len(mods)
                       and mods[i + k][0] == expected[idx])

        best = max(range(-3, 4), key=lambda k: (agree(k), -abs(k)))
        for i, (idx, _) in enumerate(host):
            j = i + best
            if 0 <= j < len(mods) and mods[j][0] == expected[idx]:
                per_step[idx].append((mods[j][2] - mods[j][1]) / 1e9)
    n = len(trace.devices)
    return {idx: (expected[idx], sum(v) / len(v))
            for idx, v in per_step.items() if len(v) == n}


def op_name(name: str) -> str:
    """The HLO instruction name of an "XLA Ops" event, whose name is the
    instruction's text: '%all-reduce.3 = f32[...] all-reduce(...)' ->
    'all-reduce.3'."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def is_collective(name: str) -> bool:
    return op_name(name).startswith(COLLECTIVES)


def collective_s(trace: Trace) -> float:
    """Device seconds of collective operations inside the window,
    averaged over the devices."""
    lo, hi = window(trace)
    devs = trace.devices
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(max(0, min(e, hi) - max(s, lo))
                     for name, s, e in trace.ops.get(d, [])
                     if is_collective(name))
    return total / len(devs) / 1e9


def op_kind(name: str) -> str:
    """'%fusion.123 = ...' -> 'fusion'; 'all-reduce-start.4' ->
    'all-reduce-start'."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


# ops whose span holds other ops of the same line: left out of the sums
CONTAINERS = ("while", "conditional", "call")


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time (summed by kind, averaged
    over devices; loops and calls left out, their bodies' operations are
    counted) and the longest idle gaps of device 0, summed by the
    innermost host event that covered each gap's middle."""
    lo, hi = window(trace)
    devs = trace.devices
    by_kind: Dict[str, float] = defaultdict(float)
    for d in devs:
        for name, s, e in trace.ops.get(d, []):
            dur = min(e, hi) - max(s, lo)
            kind = op_kind(name)
            if dur > 0 and kind not in CONTAINERS:
                by_kind[kind] += dur / len(devs) / 1e9
    device_ops = sorted(by_kind.items(), key=lambda x: -x[1])[:top]

    gaps: Dict[str, float] = defaultdict(float)
    if devs:
        mids = []
        cursor = lo
        for _, s, e in sorted(trace.ops.get(devs[0], []),
                              key=lambda x: x[1]) + [("", hi, hi)]:
            # the trace runs on past the window: clip to it
            s, e = min(max(s, lo), hi), min(e, hi)
            if s > cursor:
                mids.append(((cursor + s) // 2, (s - cursor) / 1e9))
            cursor = max(cursor, e)
        host = [h for h in trace.host if h[0] != "bench_window"]
        for (t, dur), name in zip(mids, host_activity(host, mids)):
            gaps[name] += dur
    idle_gaps = sorted(gaps.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}


def host_activity(host: List[Tuple[str, int, int, Dict]],
                  instants: List[Tuple[int, float]]) -> List[str]:
    """For each (instant, _) in increasing order, the name of the shortest
    host event covering it ("host idle" where none does).  One sweep over
    the host events, which are sorted by start."""
    out, active, i = [], [], 0
    for t, _ in instants:
        while i < len(host) and host[i][1] <= t:
            active.append(host[i])
            i += 1
        active = [h for h in active if h[2] > t]
        best = min(active, key=lambda h: h[2] - h[1], default=None)
        out.append(best[0] if best else "host idle")
    return out
