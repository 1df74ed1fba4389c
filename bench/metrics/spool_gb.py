"""Residual bytes the spool stored per window step, GB
(`SpoolStats.bytes_offloaded`)."""


def read(run):
    if not run.spool_bytes or run.window_steps == 0:
        return None
    return run.spool_bytes / 1e9 / run.window_steps
