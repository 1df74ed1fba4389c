"""Tokens trained per second: every token of the window's steps over
the whole window, from the first batch handed out to the end of the
last whole step."""


def read(run):
    if run.window_steps == 0 or run.window_s <= 0:
        return None
    return run.window_tokens / run.window_s
