"""Whole step: model operations of the traced steps (the configuration's
work module, bench/work.py by default) over their host wall time x chips
x the chip's bf16 peak, in percent."""

from stats import mfu_pct


def read(run):
    return mfu_pct(run)
