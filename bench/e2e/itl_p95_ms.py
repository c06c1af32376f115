"""Inter-token latency, 95th percentile of every gap between consecutive
output tokens of a request whose later token came in the window."""

from stats import percentile


def read(run):
    vals = [1e3 * (b - a) for r in run.requests
            for a, b in zip(r.token_times, r.token_times[1:])
            if run.t0 < b <= run.t1]
    return percentile(vals, 95)
