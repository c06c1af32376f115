"""Collectives: device time of all-reduce, all-gather, reduce-scatter and
other collective operations in the traced slice, per traced step, in ms
(averaged over the chips)."""

import trace_reduce


def read(run):
    steps = run.traced_steps
    if run.trace is None or not steps:
        return None
    return 1e3 * trace_reduce.collective_s(run.trace) / len(steps)
