"""Set-up: process start to the window's first due time, compilation,
weights and the traffic's warm-up included."""


def read(run):
    return run.setup_s
