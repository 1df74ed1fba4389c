"""What the readers of the program's step counters and host spans read,
beyond the plain numbers of `RunRecord`.

`bench/harness.run_cell` hands each metric reader the cell's
`RunRecord`, which carries the window's `StepReport`s, the program's
`repro.obs` spans of the traced steps, the host clock of the trace's
start mark and the profiler's directory. On a record that carries no
reports, or on a program whose reports lack a field, the readers get
None and read nothing.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from bench import callbacks
from bench import trace as trace_mod


def _cached(run, key: str, make):
    """make(), once per run: kept on the record for its other readers."""
    if key not in run.found:
        run.found[key] = make()
    return run.found[key]


def field_of(run, name: str) -> Optional[List[Any]]:
    """`StepReport.<name>` of each window step, or None when the record
    carries no reports or a report lacks the field."""
    if run.reports is None:
        return None
    vals = [getattr(r, name, None) for r in run.reports]
    return None if any(v is None for v in vals) else vals


def shard_sum(run, name: str) -> Optional[List[float]]:
    """Each window step's `shard_stats[*][name]` summed over shards, or
    None when no step has shard stats or one lacks the field."""
    if run.reports is None or not any(r.shard_stats for r in run.reports):
        return None
    out = []
    for r in run.reports:
        shards = r.shard_stats or {}
        if any(name not in s for s in shards.values()):
            return None
        out.append(sum(s[name] for s in shards.values()))
    return out


def spool_sum(run, name: str) -> Optional[float]:
    """`stats.<name>` summed over the window's steps, or None."""
    if run.reports is None or any(r.stats is None for r in run.reports):
        return None
    vals = [getattr(r.stats, name, None) for r in run.reports]
    return None if any(v is None for v in vals) else float(sum(vals))


def callback_split(run) -> Optional[Dict[str, Any]]:
    """`bench.callbacks.split` of the traced stretch, logged once."""
    def make():
        if run.reports is None or run.mark_ns is None or not run.prof_dir \
                or not run.traced_steps:
            return None
        path = trace_mod.find_xplane(run.prof_dir)
        if path is None:
            return None
        out = callbacks.split(trace_mod.load(path), run.spans, run.mark_ns)
        if out is not None:
            from bench.harness import log
            log(f"callback split over {run.traced_steps} traced step(s): "
                f"{ {k: v for k, v in out.items() if k != 'rest_gaps'} }")
            log(f"largest stretches left unaccounted: {out['rest_gaps']}")
        return out
    return _cached(run, "split", make)


def log_steps(run) -> None:
    """Log each window step's host-side counters once per run: step
    time, the host gap before it, dispatch time, compiles, the hooks'
    copy, offload and fetch seconds, and the spool's write time and
    bytes written."""
    def make():
        if run.reports is None:
            return None
        from bench.harness import log
        cols = {"step_s": run.step_times,
                "gap_s": [run.step_ends[i] - (run.step_ends[i - 1] if i
                                              else 0.0) - run.step_times[i]
                          for i in range(run.window_steps)],
                "dispatch_s": field_of(run, "dispatch_time"),
                "compiles": field_of(run, "compiles"),
                "copy_s": shard_sum(run, "copy_s"),
                "offload_s": shard_sum(run, "offload_s"),
                "fetch_s": shard_sum(run, "fetch_s"),
                "write_s": [getattr(r.stats, "write_time", None)
                            if r.stats is not None else None
                            for r in run.reports],
                "written_b": [r.stats.bytes_offloaded
                              if r.stats is not None else None
                              for r in run.reports]}
        for name, vals in cols.items():
            if vals is not None and any(v is not None for v in vals):
                shown = [round(v, 4) if isinstance(v, float) else v
                         for v in vals]
                log(f"window step {name}: {shown}")
        return True
    _cached(run, "logged", make)
