"""What the readers of the program's step counters and host spans read,
beyond `RunRecord`.

`bench/harness.run_cell` calls each metric reader with the cell's
`RunRecord` alone. The window's `StepReport`s, the program's `repro.obs`
spans and the profiler's files stay in `run_cell`'s locals, where they
still are while the readers run. `window(run)` finds that frame (the
one whose `rec` is `run`) and hands them out; outside it, or on a
program whose reports lack a field, the readers get None and read
nothing.

Reading another frame's locals makes Python keep a snapshot of all of
them on the frame; `window` empties it again, or the snapshot would
keep the program's state alive after the harness frees it for the
reference.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from bench import callbacks
from bench import trace as trace_mod

# the last run read, and what was found for it
_LAST: List[Any] = [None, {}]


@dataclass
class Window:
    reports: List[Any]            # the window's StepReports, in order
    spans: List[Any]              # repro.obs events of the traced steps
    mark_ns: Optional[int]        # host clock of the start mark
    prof_dir: Optional[str]


def _cached(run, key: str, make):
    if _LAST[0] is not run:
        _LAST[:] = [run, {}]
    found = _LAST[1]
    if key not in found:
        found[key] = make()
    return found[key]


def _read_window(run) -> Optional[Window]:
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "run_cell":
            loc = f.f_locals
            try:
                if loc.get("rec") is run and "win" in loc:
                    traced = loc.get("traced") or {}
                    tracer = traced.get("tracer")
                    prof_dir = loc.get("prof_dir")
                    return Window(
                        reports=list(loc["win"]),
                        spans=tracer.snapshot() if tracer else [],
                        mark_ns=traced.get("mark"),
                        prof_dir=str(prof_dir) if prof_dir else None)
            finally:
                if type(loc) is dict:   # a snapshot, not the frame's own
                    loc.clear()
        f = f.f_back
    return None


def window(run) -> Optional[Window]:
    """The harness's state for `run`, or None outside `run_cell`."""
    return _cached(run, "window", lambda: _read_window(run))


def field_of(run, name: str) -> Optional[List[Any]]:
    """`StepReport.<name>` of each window step, or None when the window
    is not found or a report lacks the field."""
    w = window(run)
    if w is None:
        return None
    vals = [getattr(r, name, None) for r in w.reports]
    return None if any(v is None for v in vals) else vals


def shard_sum(run, name: str) -> Optional[List[float]]:
    """Each window step's `shard_stats[*][name]` summed over shards, or
    None when no step has shard stats or one lacks the field."""
    w = window(run)
    if w is None or not any(r.shard_stats for r in w.reports):
        return None
    out = []
    for r in w.reports:
        shards = r.shard_stats or {}
        if any(name not in s for s in shards.values()):
            return None
        out.append(sum(s[name] for s in shards.values()))
    return out


def spool_sum(run, name: str) -> Optional[float]:
    """`stats.<name>` summed over the window's steps, or None."""
    w = window(run)
    if w is None or any(r.stats is None for r in w.reports):
        return None
    vals = [getattr(r.stats, name, None) for r in w.reports]
    return None if any(v is None for v in vals) else float(sum(vals))


def callback_split(run) -> Optional[Dict[str, Any]]:
    """`bench.callbacks.split` of the traced stretch, logged once."""
    def make():
        w = window(run)
        if w is None or w.mark_ns is None or not w.prof_dir \
                or not run.traced_steps:
            return None
        path = trace_mod.find_xplane(w.prof_dir)
        if path is None:
            return None
        out = callbacks.split(trace_mod.load(path), w.spans, w.mark_ns)
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
        w = window(run)
        if w is None:
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
                            for r in w.reports],
                "written_b": [r.stats.bytes_offloaded
                              if r.stats is not None else None
                              for r in w.reports]}
        for name, vals in cols.items():
            if vals is not None and any(v is not None for v in vals):
                shown = [round(v, 4) if isinstance(v, float) else v
                         for v in vals]
                log(f"window step {name}: {shown}")
        return True
    _cached(run, "logged", make)
