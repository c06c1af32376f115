"""Prompt tokens whose prefill the window's steps committed plus output
tokens they emitted, over the window's length."""

from stats import window_tokens


def read(run):
    tok = window_tokens(run)
    return (tok["prompt"] + tok["output"]) / (run.t1 - run.t0)
