"""Device: 1 - (union of device operation intervals / traced slice), in
percent, averaged over the chips."""

from stats import idle_pct


def read(run):
    return idle_pct(run)
