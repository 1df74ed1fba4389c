"""Host time per step dispatching the jit step, ms:
`StepReport.dispatch_time` (the step call until it returned, before the
wait for the device), over the steps after the traced ones as
host_gap_ms chooses them. Also logs the window's per-step counters."""
from bench import runstate


def read(run):
    runstate.log_steps(run)
    vals = runstate.field_of(run, "dispatch_time")
    first = run.traced_steps + 1 if run.traced_steps else 0
    if vals is None or first >= run.window_steps:
        return None
    sel = vals[first:run.window_steps]
    return 1e3 * sum(sel) / len(sel)
