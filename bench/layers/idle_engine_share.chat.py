"""Device: share (%) of device 0's idle time in the traced slice that lies
inside a `serve.step` span and outside its `serve.sync` — the chip waiting
on the engine's own host work."""

from scopes import idle_engine_pct


def read(run):
    return idle_engine_pct(run)
