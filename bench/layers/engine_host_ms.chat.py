"""Engine host: mean host time (ms) of the engine's steps that ran a
program, less the wait for their tokens — each `serve.step` span starting
in the traced slice minus its `serve.sync` child."""

from scopes import engine_host_ms


def read(run):
    return engine_host_ms(run)
