"""Kernels (both step programs stand in until kernels carry names): least
time the traced steps' work needs over their measured device time, in
percent."""

from stats import roofline_pct


def read(run):
    return roofline_pct(run, None)
