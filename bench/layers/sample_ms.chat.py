"""Kernels: device time (ms) per traced step, both step programs, of the
ops scoped `sample` (in-program sampling of the next tokens)."""

from scopes import scoped_ms, under


def read(run):
    return scoped_ms(run, {"jit_unified_step", "jit_decode_only_step"},
                     under("sample"))
