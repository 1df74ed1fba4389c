"""Host time per window step that the spool hooks spend copying the
operands of their offload callbacks, ms: the `copy_s` of each step's
`shard_stats`, summed over the window. Also logs the window's per-step
counters (copy, write time, bytes written beside step times)."""
from bench import runstate


def read(run):
    runstate.log_steps(run)
    vals = runstate.shard_sum(run, "copy_s")
    if vals is None or run.window_steps == 0:
        return None
    return 1e3 * sum(vals) / run.window_steps
