"""Step programs: device time (ms) per traced `jit_decode_only_step`
execution of the ops scoped exactly `decode_lane/layers` — what the layer
scan itself emits: the per-layer slices of the stacked weights and of the
K/V pool, and their write-back."""

from scopes import scoped_ms


def read(run):
    return scoped_ms(run, {"jit_decode_only_step"},
                     lambda path: path == "decode_lane/layers")
