"""Scheduler: mean over the window's steps of decoding slots / max_slots
(the `ServeMetrics` slot-occupancy arithmetic), in percent."""


def read(run):
    steps = run.window_steps
    if not steps:
        return None
    slots = run.config["runtime"]["max_slots"]
    return 100.0 * sum(len(s.decode_keys) for s in steps) / (len(steps) * slots)
