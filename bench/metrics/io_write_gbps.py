"""The rate one spool write sees, GB/s: the window's
`SpoolStats.bytes_offloaded` over its `write_time` (seconds inside the
backend's write, summed over the store workers)."""
from bench import runstate


def read(run):
    written = runstate.spool_sum(run, "bytes_offloaded")
    seconds = runstate.spool_sum(run, "write_time")
    if not written or not seconds:
        return None
    return written / seconds / 1e9
