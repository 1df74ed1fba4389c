"""The device's idle time split by the spool's host callbacks, on the
profiler trace's clock.

A spool step spends most of its time with no operation on the device.
Within the stretch the harness marks (`bench/trace.py`), the device's
time falls into:

  busy          an operation runs (leaves of the "XLA Ops" line, host
                transfers left out, as `trace.reduce` counts it)
  hook_exposed  idle while a `hostcb.*` span of the program is open: the
                device waits on a hook's Python body
  hostcb_xfer   idle inside a host-transfer operation (a callback's
                send-done / recv-done, `is_host_transfer=true`) with no
                `hostcb.*` span open: the link and the runtime's
                hand-off on either side of the Python body
  rest          idle and none of these

The program's spans run on the host's `perf_counter_ns`. They are moved
onto the trace's clock by the harness's two marks: the start mark is
taken on both clocks, and the end mark lies inside the program's last
`loader.next` span (the harness stops the profiler as it hands out a
batch), whose start is its host time. `drift_ns` is how far the end
mark falls from where the start mark alone puts it; when the last
`loader.next` does not hold the end mark (the window ended first), the
start mark alone maps the spans and `drift_ns` is None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

Interval = Tuple[float, float]
#: the end mark's host time is trusted when the start mark alone puts it
#: within this many ns of the trace's end mark
MAX_DRIFT_NS = 20e6
HOSTCB = "hostcb."
FETCH_CB = "hostcb.fetch_cb"
LOADER_NEXT = "loader.next"
TOP = 5


def intersect(a: Sequence[Interval], b: Sequence[Interval]) \
        -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(a: Sequence[Interval], lo: float, hi: float) \
        -> List[Interval]:
    """[lo, hi) less a sorted list of disjoint intervals."""
    out, t = [], lo
    for x, y in a:
        if x > t:
            out.append((t, min(x, hi)))
        t = max(t, y)
        if t >= hi:
            break
    if hi > t:
        out.append((t, hi))
    return out


def clock_map(spans, mark_ns: float, win: Interval):
    """(to_trace, drift_ns): a function from host ns to trace ns, and the
    end mark's drift (None when only the start mark could be used)."""
    lo, hi = win
    nexts = [ev for ev in spans if ev[0] == LOADER_NEXT]
    if nexts:
        end_host = max(ev[2] for ev in nexts)
        drift = (hi - lo) - (end_host - mark_ns)
        if abs(drift) <= MAX_DRIFT_NS and end_host > mark_ns:
            rate = (hi - lo) / (end_host - mark_ns)
            return (lambda t: lo + (t - mark_ns) * rate), drift
    return (lambda t: lo + (t - mark_ns)), None


def device_intervals(pd, win: Interval) \
        -> Optional[Tuple[List[Interval], List[Interval]]]:
    """(busy, host transfers) of the first device plane that ran an
    operation in the stretch, each a union clipped to it."""
    lo, hi = win
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        busy, xfer = [], []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for name, a, b in trace.leaves(line.events):
                a, b = max(a, lo), min(b, hi)
                if b > a:
                    (xfer if trace.HOST_TRANSFER in name
                     else busy).append((a, b))
        if busy:
            return trace.union(busy), trace.union(xfer)
    return None


def split(pd, spans, mark_ns: float) -> Optional[Dict]:
    """The split of the marked stretch (seconds), the clock check and
    the largest stretches left unaccounted, or None when the trace has
    no marks or no device operation in them. `spans` are the program's
    `repro.obs` events (name, cat, ts_ns, dur_ns, args)."""
    win = trace.marks(pd)
    if win is None:
        return None
    dev = device_intervals(pd, win)
    if dev is None:
        return None
    busy, xfer = dev
    lo, hi = win
    spans = [ev for ev in spans if ev[3] >= 0]
    to_trace, drift = clock_map(spans, mark_ns, win)
    mapped = [(ev[0], to_trace(ev[2]), to_trace(ev[2] + ev[3]))
              for ev in spans]
    cb = trace.union((max(a, lo), min(b, hi)) for name, a, b in mapped
                     if name.startswith(HOSTCB) and min(b, hi) > max(a, lo))
    idle = complement(busy, lo, hi)
    exposed = intersect(idle, cb)
    xfer_only = intersect(intersect(idle, xfer), complement(cb, lo, hi))
    rest = complement(trace.union(busy + exposed + xfer_only), lo, hi)
    host = trace.host_events(pd) + [m for m in mapped if m[2] > m[1]]
    largest = sorted(rest, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": trace.total(busy) * 1e-9,
        "hook_exposed_s": trace.total(exposed) * 1e-9,
        "hostcb_xfer_s": trace.total(xfer_only) * 1e-9,
        "rest_s": trace.total(rest) * 1e-9,
        "drift_ns": drift,
        "fetch_cb_outside_ns": outside(
            [(a, b) for name, a, b in mapped if name == FETCH_CB], xfer),
        "rest_gaps": [[trace.name_gap(a, b, host), (b - a) * 1e-9]
                      for a, b in largest],
    }


def outside(cbs: Sequence[Interval], xfer: Sequence[Interval]) \
        -> Optional[float]:
    """The largest distance (ns) by which a callback span reaches past
    the host-transfer operation that overlaps it most (the nearest one
    when none overlaps), or None with no callback or no transfer."""
    if not cbs or not xfer:
        return None
    worst = 0.0
    for a, b in cbs:
        op = max(xfer, key=lambda x: (min(b, x[1]) - max(a, x[0]),
                                      -abs(x[0] - a)))
        worst = max(worst, op[0] - a, b - op[1])
    return worst
