"""Time to first token, 90th percentile over every request due in the
window, timed from its due time; a request that never got its first token
enters as +inf (and is reported as failed).  The chat window holds twenty
requests, so the two slowest lie beyond it."""

import math

from stats import percentile


def read(run):
    vals = [r.token_times[0] - r.due if r.token_times else math.inf
            for r in run.requests if run.t0 <= r.due < run.t1]
    v = percentile(vals, 90)
    return v if v is not None and math.isfinite(v) else None
