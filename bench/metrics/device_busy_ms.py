"""Device busy time per traced step, ms: the union of device operation
intervals in the profiler trace over the whole steps traced."""


def read(run):
    t = run.trace
    if not t or run.traced_steps == 0:
        return None
    return 1e3 * t["busy_s"] / run.traced_steps
