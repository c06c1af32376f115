"""A work module for the tests: every step needs 10^12 operations and
10^9 bytes, whatever it holds."""


def step_work(cfg, decode_keys, segments):
    return 10**12, 10**9
