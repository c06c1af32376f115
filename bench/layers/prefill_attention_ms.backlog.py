"""Kernels: device time (ms) per traced `jit_unified_step` execution of
the ops scoped `prefill_lane/layers/attention` (every layer), averaged
over the chips."""

from scopes import scoped_ms, under


def read(run):
    return scoped_ms(run, {"jit_unified_step"},
                     under("prefill_lane/layers/attention"))
