"""Tokens trained per second: every token of every step in the window,
over the window's seconds (host clock)."""


def read(run):
    return run.steps * run.tokens_per_step / run.window_s
