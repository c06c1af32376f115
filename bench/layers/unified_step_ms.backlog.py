"""Step programs: mean device time of the unified step (`jit_unified_step`)
over the traced steps."""

from stats import program_ms


def read(run):
    return program_ms(run, "jit_unified_step")
