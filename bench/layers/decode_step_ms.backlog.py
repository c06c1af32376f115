"""Step programs: mean device time of the decode-only step
(`jit_decode_only_step`) over the traced steps: the steps a backlog runs
once its slots are full and no prompt can be admitted."""

from stats import program_ms


def read(run):
    return program_ms(run, "jit_decode_only_step")
