"""Kernels: device time (ms) per traced `jit_decode_only_step` execution
of the ops scoped `decode_lane/layers/attention` (every layer)."""

from scopes import scoped_ms, under


def read(run):
    return scoped_ms(run, {"jit_decode_only_step"},
                     under("decode_lane/layers/attention"))
