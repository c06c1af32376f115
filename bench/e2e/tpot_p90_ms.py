"""Time per output token, 90th percentile over the requests that finished
in the window: (last token time - first token time) / (tokens - 1)."""

from stats import percentile


def read(run):
    vals = [1e3 * (r.token_times[-1] - r.token_times[0])
            / (len(r.token_times) - 1)
            for r in run.requests
            if r.finished is not None and run.t0 < r.finished <= run.t1
            and len(r.token_times) > 1]
    return percentile(vals, 90)
