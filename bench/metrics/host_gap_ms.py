"""Window time per step outside `StepReport.step_time`, ms: the loader,
the session's per-step bookkeeping and reports between steps. Read over
the steps after the traced ones, so the profiler and the program's span
recorder, which run only while the trace lasts, add nothing to it; the
step in whose gap the trace stops is left out too."""


def read(run):
    first = run.traced_steps + 1 if run.traced_steps else 0
    gaps = [run.step_ends[i] - (run.step_ends[i - 1] if i else 0.0)
            - run.step_times[i]
            for i in range(first, run.window_steps)]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
