"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time.

The harness marks the traced stretch with two host annotations,
`bench.trace_start` and `bench.trace_end`, recorded into the trace by
`jax.profiler.TraceAnnotation`. Within that stretch:

  busy_s      union of the intervals in which an operation ran on a
              device, averaged over the devices. Operations are the
              leaves of the "XLA Ops" line of each device plane: a loop
              or call that holds other operations is not counted itself,
              and a host transfer (a host callback's send-done /
              recv-done, `is_host_transfer=true`) is the device waiting
              for the host, so it counts as idle
  window_s    length of the stretch
  device_ops  the operations that took most device time, summed by HLO
              instruction name without its numeric suffix
  idle_gaps   the longest stretches in which no device operation ran,
              each named by the host activity that overlaps it most: an
              event of the trace's host planes or one of the program's
              own spans (`repro.obs`), handed in on the trace's clock
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import re

START_MARK = "bench.trace_start"
END_MARK = "bench.trace_end"
OPS_LINE = "XLA Ops"
HOST_TRANSFER = "is_host_transfer=true"
TOP = 10
_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion`."""
    return _SUFFIX.sub("", hlo.split(" = ", 1)[0].strip().lstrip("%"))


def leaves(events) -> List[Tuple[str, float, float]]:
    """(hlo text, start, end) of the events that hold no other event."""
    evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in events), key=lambda t: (t[0], -t[1]))
    out = []
    for i, (a, b, name) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][0] < b:
            continue                       # holds the next one
        out.append((name, a, b))
    return out

Interval = Tuple[float, float]


def start(log_dir: str) -> None:
    """Start the profiler with host annotations kept and the Python
    tracer off (it would time every Python call of the host path)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def marks(pd) -> Optional[Interval]:
    """(start, end) in ns of the traced stretch, from the harness's
    annotations on the host planes."""
    start = end = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == START_MARK:
                    t = ev.start_ns
                    start = t if start is None else min(start, t)
                elif ev.name == END_MARK:
                    t = ev.start_ns
                    end = t if end is None else max(end, t)
    if start is None or end is None or end <= start:
        return None
    return start, end


def host_events(pd) -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) of every host-plane event with a length,
    the harness's marks left out."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0 and ev.name not in (START_MARK,
                                                          END_MARK):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def name_gap(a: float, b: float,
             host: Sequence[Tuple[str, float, float]]) -> str:
    """The host activity of an idle gap [a, b): the shortest host event
    that covers at least half of it (so the innermost span, not the
    step around it), else the one that overlaps it most."""
    best, best_len = None, None
    most, most_ov = "unattributed", 0.0
    for name, s, e in host:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        if 2 * ov >= b - a and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
        if ov > most_ov:
            most, most_ov = name, ov
    return best if best is not None else most


def reduce(pd, extra_host: Sequence[Tuple[str, float, float]] = ()) \
        -> Optional[Dict]:
    """Busy, idle and the breakdown of the marked stretch, or None when
    the trace holds no marks or no device operation in them."""
    win = marks(pd)
    if win is None:
        return None
    lo, hi = win
    per_dev: List[List[Interval]] = []
    by_op: Dict[str, float] = defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for name, a, b in leaves(line.events):
                if HOST_TRANSFER in name:
                    continue
                c = _clip(a, b, lo, hi)
                if c is None:
                    continue
                ivs.append(c)
                by_op[op_name(name)] += (c[1] - c[0]) * 1e-9
        if ivs:
            per_dev.append(union(ivs))
    if not per_dev:
        return None
    busy = sum(total(u) for u in per_dev) / len(per_dev) * 1e-9
    # idle gaps of the first device that ran anything
    busy0 = per_dev[0]
    gaps = []
    t = lo
    for a, b in busy0:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = list(host_events(pd)) + list(extra_host)
    named = [[name_gap(a, b, host), (b - a) * 1e-9]
             for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy, "window_s": (hi - lo) * 1e-9,
            "devices": len(per_dev),
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": named}
