"""Scheduler: queue wait (admission - due time), 90th percentile over the
requests due in the window; one never admitted enters as +inf."""

import math

from stats import percentile


def read(run):
    vals = [r.admitted - r.due if r.admitted is not None else math.inf
            for r in run.requests if run.t0 <= r.due < run.t1]
    v = percentile(vals, 90)
    return v if v is not None and math.isfinite(v) else None
