"""The program's own spans and scopes in a reduced trace (`trace_reduce`).

The serving runtime opens host spans on the profiler's clock (`serve.step`
around each engine step, `serve.sync` around the read-back of its tokens,
...) and names the model part of every op of its step programs: a table
of instruction name to scope path per HLO module name,
`repro.serve.trace.program_scopes()`, kept by the serving process.

Device ops: each op of a device's "XLA Ops" line is given to the module
execution ("XLA Modules" line) that holds it on the same device, and named
through that module's table ("" where the table has no scope for it).  An
op that holds other ops of its line (a `while`, a `closed_call`) is left
out, so nothing is counted twice.  Executions are those that start in the
traced slice; values are averaged over the chips.

Every function returns None where the trace holds no `serve.step` span or
the program keeps no table: a program without them reads nothing.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import trace_reduce

Host = Tuple[str, int, int, Dict]


def program_scopes() -> Dict[str, Dict[str, str]]:
    """The serving process's scope tables; {} where it keeps none."""
    try:
        from repro.serve.trace import program_scopes as tables
    except ImportError:
        return {}
    return tables()


def step_spans(trace) -> List[Tuple[Host, Optional[Host]]]:
    """Each `serve.step` span that starts in the traced slice, with its
    `serve.sync` child (None for a step that ran no program)."""
    lo, hi = trace_reduce.window(trace)
    steps = [h for h in trace.host if h[0] == "serve.step" and lo <= h[1] < hi]
    syncs = [h for h in trace.host if h[0] == "serve.sync"]
    starts = [h[1] for h in syncs]
    out = []
    for st in steps:
        i = bisect.bisect_left(starts, st[1])
        child = syncs[i] if i < len(syncs) and syncs[i][2] <= st[2] else None
        out.append((st, child))
    return out


def engine_host_ms(run) -> Optional[float]:
    """Mean host time (ms) of the engine's steps that ran a program, less
    the wait for their tokens: `serve.step` minus its `serve.sync`."""
    if run.trace is None:
        return None
    ms = [(st[2] - st[1] - (sy[2] - sy[1])) / 1e6
          for st, sy in step_spans(run.trace) if sy is not None]
    return sum(ms) / len(ms) if ms else None


def _clip_union(spans: List[Tuple[int, int]], lo: int,
                hi: int) -> List[Tuple[int, int]]:
    """Sorted disjoint intervals covering the spans, clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted(spans):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_engine_pct(run) -> Optional[float]:
    """Share (%) of device 0's idle time in the traced slice that falls
    inside a `serve.step` span and outside its `serve.sync`: the device
    waits on the engine's own host work."""
    if run.trace is None or not run.trace.devices:
        return None
    spans = step_spans(run.trace)
    if not spans:
        return None
    lo, hi = trace_reduce.window(run.trace)
    busy = _clip_union([(s, e) for _, s, e in
                        run.trace.ops.get(run.trace.devices[0], [])], lo, hi)
    idle, cur = [], lo
    for s, e in busy:
        if s > cur:
            idle.append((cur, s))
        cur = e
    if cur < hi:
        idle.append((cur, hi))
    engine = []
    for st, sy in spans:
        if sy is None:
            engine.append((st[1], st[2]))
        else:
            engine += [(st[1], sy[1]), (sy[2], st[2])]
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    return 100.0 * _overlap(idle, _clip_union(engine, lo, hi)) / idle_ns


def leaf_ops(ops: List[trace_reduce.Span]) -> List[trace_reduce.Span]:
    """The ops of one line that hold no other op of it.  Ops sorted by
    start (ties: the longer first) nest, so an op holds another exactly
    when the next op starts before it ends."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < o[2] and nxt[2] <= o[2])]


def executions(run) -> Optional[Dict[int, List[Tuple[str, Dict[str, int]]]]]:
    """Per device, each module execution that starts in the traced slice:
    (module name, {scope path: device ns of its leaf ops})."""
    tables = program_scopes()
    if run.trace is None or not tables or not step_spans(run.trace):
        return None
    lo, hi = trace_reduce.window(run.trace)
    out: Dict[int, List[Tuple[str, Dict[str, int]]]] = {}
    for d in run.trace.devices:
        mods = sorted(run.trace.modules.get(d, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        per: Dict[int, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
        for name, s, e in leaf_ops(run.trace.ops.get(d, [])):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][2] or not lo <= mods[i][1] < hi:
                continue
            module = trace_reduce.module_name(mods[i][0])
            path = tables.get(module, {}).get(trace_reduce.op_name(name), "")
            per[i][path] += e - s
        out[d] = [(trace_reduce.module_name(mods[i][0]), dict(per[i]))
                  for i in sorted(per)]
    return out


def under(prefix: str) -> Callable[[str], bool]:
    """Paths at or below `prefix`."""
    return lambda path: path == prefix or path.startswith(prefix + "/")


def scoped_ms(run, modules, match: Callable[[str], bool]) -> Optional[float]:
    """Device time (ms) per execution of `modules` of the ops whose scope
    path `match`es, averaged over the chips."""
    ex = executions(run)
    if not ex:
        return None
    per_dev = []
    for runs in ex.values():
        runs = [paths for module, paths in runs if module in modules]
        if runs:
            ns = sum(v for paths in runs for p, v in paths.items()
                     if match(p))
            per_dev.append(ns / len(runs) / 1e6)
    return sum(per_dev) / len(per_dev) if per_dev else None


def by_scope_ms(run, module: str) -> Optional[Dict[str, float]]:
    """Device time (ms) per execution of `module`, by scope path ("" for
    ops with no scope), averaged over the chips."""
    ex = executions(run)
    if not ex:
        return None
    total: Dict[str, float] = defaultdict(float)
    devs = 0
    for runs in ex.values():
        runs = [paths for m, paths in runs if m == module]
        if not runs:
            continue
        devs += 1
        for paths in runs:
            for p, v in paths.items():
                total[p] += v / len(runs) / 1e6
    return {p: v / devs for p, v in total.items()} if devs else None
