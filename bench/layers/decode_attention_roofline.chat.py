"""Kernels: least time the decode attention's work needs over its device
time, in percent.  The work of a traced decode-only step: per decoded
token, `attention_flops` and `kv_bytes_per_token` times the keys it
attended (`Step.decode_keys`), every layer (bench/work.py), at the chip's
peaks; the time: `decode_attention_ms.chat`."""

import work
from scopes import scoped_ms, under


def read(run):
    steps = [s for s in run.traced_steps if s.kind == "decode_only"]
    ms = scoped_ms(run, {"jit_decode_only_step"},
                   under("decode_lane/layers/attention"))
    if not steps or not ms or run.peaks is None:
        return None
    cfg = run.config
    need = sum(work.least_time_s(
        sum(work.attention_flops(cfg, k) for k in s.decode_keys),
        sum(k * work.kv_bytes_per_token(cfg) for k in s.decode_keys),
        run.peaks, run.chips)[0] for s in steps) / len(steps)
    return 100.0 * need / (ms / 1e3)
