"""Tokens of unmasked local steps over the whole window, per second."""


def read(run):
    return run.useful_tokens / run.window_s
