"""The readers of the program's step counters and host-callback spans
(bench/runstate.py, bench/callbacks.py, the metrics they feed, and the
pending metrics of bench/pending.json): on a recorded window, on
hand-made traces, and in whole tiny runs on the CPU."""
import gc
import json
import math
import time
import weakref
from dataclasses import dataclass, field
from types import SimpleNamespace as NS
from typing import List

import pytest

from bench import callbacks, harness, runstate, trace
from repro.core.spool import SpoolStats


# ------------------------------------------------------- a recorded run

def recorded_run(**kw):
    base = dict(
        workload={}, chips=1, setup_s=42.5, window_s=10.0, window_steps=4,
        window_tokens=4096, step_times=[2.0, 2.0, 2.5, 2.5],
        step_ends=[2.5, 4.6, 7.3, 10.0],
        flops_per_token=3.0e9, peak={"bf16_flops_per_s": 197e12},
        peak_bytes_in_use=9 << 30,
        compiled={"argument": 8 << 30, "output": 8 << 30,
                  "alias": 8 << 30, "temp": 3 << 30},
        spool_bytes=8_000_000_000, trace=None, traced_steps=2)
    base.update(kw)
    return harness.RunRecord(**base)


def report(i):
    return NS(dispatch_time=0.001 * (i + 1), compiles=[1, 0, 2, 0][i],
              shard_stats={"global": {"copy_s": 0.1 * (i + 1)}},
              stats=SpoolStats(bytes_offloaded=2_000_000_000,
                               write_time=1.0 + i))


def read_window(rec, win, traced, prof_dir, name):
    """Reads `name` from `rec` carrying a window as the harness's run
    hands it over: its reports, the tracer's spans, the start mark and
    the profiler's directory."""
    tracer = traced.get("tracer")
    rec.reports = win
    rec.spans = tracer.snapshot() if tracer else []
    rec.mark_ns = traced.get("mark")
    rec.prof_dir = prof_dir
    return harness.metric_reader(name)(rec)


READ = {
    # steps after the two traced ones, and the one in whose gap the
    # trace stopped: step 3 alone
    "dispatch_ms": 4.0,
    "window_compiles": 3,
    "hook_copy_ms": 1e3 * (0.1 + 0.2 + 0.3 + 0.4) / 4,
    "io_write_gbps": 8e9 / (1.0 + 2.0 + 3.0 + 4.0) / 1e9,
}


@pytest.mark.parametrize("name", sorted(READ))
def test_readers_on_a_recorded_window(name):
    rec = recorded_run()
    got = read_window(rec, [report(i) for i in range(4)], {}, None, name)
    assert got == pytest.approx(READ[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(READ) + ["hook_exposed_ms",
                                                 "hostcb_xfer_ms"])
def test_readers_find_nothing_outside_the_harness_or_on_older_reports(
        name):
    assert harness.metric_reader(name)(recorded_run()) is None
    # a program whose reports carry none of the new fields
    old = [NS(shard_stats={"global": {"offloads": 1}},
              stats=NS(bytes_offloaded=1)) for _ in range(4)]
    assert read_window(recorded_run(), old, {}, None, name) is None


def test_readers_keep_nothing_the_harness_frees():
    """The harness frees the program's state before the reference runs;
    a reader must not keep that state alive, nor the record it read."""
    class State:
        pass

    def read_all(rec, win):
        state = State()
        kept = weakref.ref(state)
        rec.reports = win
        for name in READ:
            harness.metric_reader(name)(rec)
        del state
        gc.collect()
        return kept()

    rec = recorded_run()
    assert read_all(rec, [report(i) for i in range(4)]) is None
    read = weakref.ref(rec)
    del rec
    gc.collect()
    assert read() is None


def test_dispatch_reads_nothing_when_every_step_was_traced():
    rec = recorded_run(traced_steps=3)
    assert read_window(rec, [report(i) for i in range(4)], {}, None,
                    "dispatch_ms") is None


# ------------------------------------------------------ hand-made trace

@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclass
class Line:
    name: str
    events: List[Ev]


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


@dataclass
class Profile:
    planes: List[Plane]


MARK_NS = 5000          # host clock of the start mark (trace clock 0)


def profile(end=1000):
    """Device: fusion, a callback's send-done, fusion, a recv-done,
    fusion; the harness's marks at 0 and `end` on the trace's clock."""
    xfer = ", is_host_transfer=true"
    ops = Line(trace.OPS_LINE, [
        Ev("%fusion.1 = f", 0, 100),
        Ev(f"%send-done.2 = token[] send-done(...){xfer}", 100, 300),
        Ev("%fusion.3 = f", 400, 100),
        Ev(f"%recv-done.4 = f32[8] recv-done(...){xfer}", 500, 400),
        Ev("%fusion.5 = f", 900, 100),
    ])
    host = Plane("/host:CPU", [Line("python", [
        Ev(trace.START_MARK, 0, 1), Ev(trace.END_MARK, end, 1),
        Ev("ScheduleWork", 1000, 0)])])
    return Profile([host, Plane("/device:TPU:0", [ops])])


def span(name, a, b):
    return (name, "t", a, b - a, {})


def spans(end_host=6000, fetch_end=5850):
    return [span("hostcb.offload_cb", 5150, 5350),
            span("hostcb.fetch_cb", 5600, fetch_end),
            span("loader.next", end_host, end_host + 300)]


def test_callback_split_accounts_for_the_idle_device():
    s = callbacks.split(profile(), spans(), MARK_NS)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(300e-9)
    # idle under the two callback bodies: [150, 350) and [600, 850)
    assert s["hook_exposed_s"] == pytest.approx(450e-9)
    # inside the transfers with no body open: 50 + 50 + 100 + 50
    assert s["hostcb_xfer_s"] == pytest.approx(250e-9)
    assert s["rest_s"] == pytest.approx(0.0, abs=1e-15)
    assert s["drift_ns"] == 0
    assert s["fetch_cb_outside_ns"] == 0


def test_callback_split_maps_by_both_marks_and_names_the_rest():
    # the end mark's host time is 10 ns later than the start mark alone
    # puts it: the rate maps [5150, 5350) to [148.5, 346.5) and so on
    s = callbacks.split(profile(), spans(end_host=6010), MARK_NS)
    assert s["drift_ns"] == -10
    r = 1000 / 1010
    exposed = (350 * r - 150 * r) + (850 * r - 600 * r)
    assert s["hook_exposed_s"] == pytest.approx(exposed * 1e-9)
    assert s["busy_s"] + s["hook_exposed_s"] + s["hostcb_xfer_s"] + \
        s["rest_s"] == pytest.approx(s["window_s"])
    # a fetch body that runs 50 ns past its recv-done
    s = callbacks.split(profile(), spans(fetch_end=5950), MARK_NS)
    assert s["fetch_cb_outside_ns"] == pytest.approx(50)
    # without a loader.next span near the end mark, the start mark alone
    s = callbacks.split(profile(), spans(end_host=9e9), MARK_NS)
    assert s["drift_ns"] is None
    assert s["hook_exposed_s"] == pytest.approx(450e-9)


def test_idle_without_a_callback_or_transfer_is_left_over_and_named():
    pd = profile()
    # the recv-done ends at 700: [700, 900) is idle, no transfer, and
    # an io.write span of the program covers it
    pd.planes[1].lines[0].events[3] = Ev(
        "%recv-done.4 = f32[8] recv-done(...), is_host_transfer=true",
        500, 200)
    s = callbacks.split(pd, spans(fetch_end=5650) +
                        [span("io.write", 5690, 5910)], MARK_NS)
    assert s["rest_s"] == pytest.approx(200e-9)
    assert s["rest_gaps"] == [["io.write", pytest.approx(200e-9)]]


def test_split_reads_nothing_without_marks_or_device_ops():
    pd = profile()
    assert callbacks.split(Profile([pd.planes[1]]), spans(),
                           MARK_NS) is None
    assert callbacks.split(Profile([pd.planes[0]]), spans(),
                           MARK_NS) is None


def test_device_clock_readers_divide_by_traced_steps(monkeypatch):
    monkeypatch.setattr(runstate.trace_mod, "find_xplane",
                        lambda d: "trace.xplane.pb")
    monkeypatch.setattr(runstate.trace_mod, "load", lambda p: profile())
    rec = recorded_run(traced_steps=2)
    tracer = NS(snapshot=spans)
    traced = {"tracer": tracer, "mark": MARK_NS}
    win = [report(i) for i in range(4)]
    assert read_window(rec, win, traced, "/prof", "hook_exposed_ms") == \
        pytest.approx(1e3 * 450e-9 / 2)
    assert read_window(rec, win, traced, "/prof", "hostcb_xfer_ms") == \
        pytest.approx(1e3 * 250e-9 / 2)


# ------------------------------------------------------- whole tiny runs

def test_tiny_remat_run_reads_dispatch_and_compiles(tiny_bench):
    res = harness.run_cell("tiny.remat", 2 ** 31 + 11, 1.0, True,
                           time.perf_counter())
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    assert 0 < m["dispatch_ms"]["value"] and \
        math.isfinite(m["dispatch_ms"]["value"])
    assert m["window_compiles"]["value"] == 0
    assert "io_write_gbps" not in m


def test_tiny_spool_run_reads_the_pending_metrics(tiny_bench, monkeypatch):
    from bench import pending
    spool = "tiny.spool"
    # the real list, kept to the tiny spool cell
    real = json.loads((pending.BENCH / "pending.json").read_text())
    for m in real["per_layer"]:
        m["workloads"] = [spool]
    real["workloads_added"] = {k: [spool]
                               for k in real["workloads_added"]}
    (harness.BENCH / "pending.json").write_text(json.dumps(real))
    monkeypatch.setattr(harness, "load_benchmark", pending.load_benchmark)
    res = harness.run_cell(spool, 2 ** 31 + 13, 1.0, True,
                           time.perf_counter())
    m = res["metrics"]
    assert m["hook_copy_ms"]["value"] > 0
    assert m["io_write_gbps"]["value"] > 0
    assert m["window_compiles"]["value"] == 0
    assert "dispatch_ms" not in m
    json.dumps(res)
