"""Device idle time per traced step while a spool hook's Python body
runs, ms: idle on the profiler trace's clock with a `hostcb.*` span of
the program open (bench/callbacks.py)."""
from bench import runstate


def read(run):
    split = runstate.callback_split(run)
    if split is None:
        return None
    return 1e3 * split["hook_exposed_s"] / run.traced_steps
