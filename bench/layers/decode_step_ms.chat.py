"""Step programs: mean device time of the decode-only step
(`jit_decode_only_step`) over the traced steps."""

from stats import program_ms


def read(run):
    return program_ms(run, "jit_decode_only_step")
