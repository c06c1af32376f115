"""Kernels (the whole decode-only program stands in until kernels carry
names): least time the traced decode-only steps' work needs over their
measured device time, in percent."""

from stats import roofline_pct


def read(run):
    return roofline_pct(run, "jit_decode_only_step")
