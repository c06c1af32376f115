"""Arithmetic the metric readers share.

`percentile` is the nearest-rank percentile of `repro.serve.metrics`,
copied so that the yardstick stays with the benchmark: the value at rank
ceil(p/100 * n), 1-based.  A request that never reached the event a
latency times enters as +inf, so it counts against the tail.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import modules


def percentile(values: List[float], p: float) -> Optional[float]:
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[min(len(xs), rank) - 1]


def program_ms(run, module: str) -> Optional[float]:
    """Mean device time (ms) of the traced steps that ran `module`."""
    ts = [s for name, s in (run.step_device or {}).values() if name == module]
    return 1e3 * sum(ts) / len(ts) if ts else None


def roofline_pct(run, module: Optional[str]) -> Optional[float]:
    """Least time the traced steps' work needs (the `step_work` of the
    configuration's work module, at the chip's peaks) over their measured
    device time, in percent; `module` None takes the steps of every
    program."""
    import work

    step_work = modules.of_config(run.config, "work").step_work
    need = spent = 0.0
    for s in run.traced_steps:
        name, dev_s = run.step_device[s.idx]
        if module is not None and name != module:
            continue
        flops, nbytes = step_work(run.config, s.decode_keys, s.segments)
        need += work.least_time_s(flops, nbytes, run.peaks, run.chips)[0]
        spent += dev_s
    return 100.0 * need / spent if spent > 0 else None


def mfu_pct(run) -> Optional[float]:
    """Model operations of the traced steps (the configuration's work
    module) over their host wall time times the chips' peak, in
    percent."""
    step_work = modules.of_config(run.config, "work").step_work
    flops = wall = 0.0
    for s in run.traced_steps:
        flops += step_work(run.config, s.decode_keys, s.segments)[0]
        wall += s.t_end - s.t_begin
    if wall <= 0:
        return None
    return 100.0 * flops / (wall * run.chips * run.peaks["bf16_flops_per_s"])


def idle_pct(run) -> Optional[float]:
    import trace_reduce

    if run.trace is None or not run.trace.devices:
        return None
    w = trace_reduce.window_s(run.trace)
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace) / w) if w > 0 else None


def window_tokens(run) -> Dict[str, int]:
    """Prompt tokens committed and output tokens emitted by the window's
    steps."""
    steps = run.window_steps
    return {"prompt": sum(n for s in steps for _, n, _ in s.segments),
            "output": sum(s.new_tokens for s in steps)}
