"""Seconds from the start of the process to the first batch of the
window: imports, weights, the session, compilation and the set-up
steps."""


def read(run):
    return run.setup_s
