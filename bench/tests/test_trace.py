"""The trace reduction (bench/trace.py) on a trace recorded on a TPU v5e
by record_trace.py (three steps of a jitted matmul chain, each followed
by 50 ms of host sleep), and on hand-made planes for what that trace
does not hold: loops around operations and host-callback waits."""
from dataclasses import dataclass, field
from pathlib import Path
from typing import List

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "three_steps.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(trace.load(str(DATA)))


def test_window_is_between_the_marks(recorded):
    # bench.trace_start at 49027208 ns, bench.trace_end at 203749403 ns
    assert recorded["window_s"] == pytest.approx(154_722_195e-9, abs=1e-12)
    assert recorded["devices"] == 1


def test_busy_is_the_union_of_device_ops_in_the_window(recorded):
    # the device clock of this trace runs ~1.2 ms early against the
    # host's, so step 1's operations (47.93-48.11 ms) fall before the
    # start mark; steps 2 and 3 each hold copy-start/done, the tanh
    # fusion and the output fusion: 16 + 89953 + 90877 and
    # 14 + 3 + 89952 + 90896 ns
    assert recorded["busy_s"] == pytest.approx(361_711e-9, abs=1e-12)


def test_breakdown_names_ops_and_gaps(recorded):
    ops = dict(recorded["device_ops"])
    assert set(ops) == {"convolution_tanh_fusion", "fusion", "copy-start",
                        "copy-done"}
    assert ops["fusion"] == pytest.approx((90877 + 90896) * 1e-9)
    gaps = recorded["idle_gaps"]
    assert len(gaps) <= trace.TOP
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    # the host slept 50 ms after each step
    assert gaps[0][0] == "host.sleep" and 0.05 < gaps[0][1] < 0.06
    idle = recorded["window_s"] - recorded["busy_s"]
    assert sum(g[1] for g in gaps) <= idle + 1e-9


# --------------------------------------------------------- hand-made

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


def marks(a, b):
    return Plane("/host:CPU", [Line("python", [
        Ev(trace.START_MARK, a, 1), Ev(trace.END_MARK, b, 1),
        Ev("hook.fetch", 300, 400), Ev("engine.step", 0, 1000)])])


def test_loops_count_by_their_leaves_and_host_waits_are_idle():
    ops = Line(trace.OPS_LINE, [
        Ev("%while.3 = (...) while(...)", 100, 800),
        Ev("%fusion.1 = bf16[8] fusion(...)", 100, 200),
        Ev("%cb.7 = token[] recv-done(...), is_host_transfer=true", 300,
           400),
        Ev("%fusion.2 = bf16[8] fusion(...)", 700, 100),
        Ev("%copy.9 = bf16[8] copy(...)", 950, 100),   # past the window
    ])
    pd = Profile([marks(0, 1000), Plane("/device:TPU:0", [ops])])
    r = trace.reduce(pd)
    assert r["window_s"] == pytest.approx(1e-6)
    # fusion 100-300, fusion 700-800, copy 950-1000
    assert r["busy_s"] == pytest.approx(350e-9)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion": 300e-9, "copy": 50e-9})
    # the longest gap is the host-callback wait, named by the innermost
    # host span over it, not by the step around it
    assert r["idle_gaps"][0] == ["hook.fetch", pytest.approx(400e-9)]


def test_program_spans_handed_in_name_gaps():
    ops = Line(trace.OPS_LINE, [Ev("%fusion.1 = f", 0, 10),
                                Ev("%fusion.2 = f", 90, 10)])
    pd = Profile([Plane("/host:CPU", [Line("python", [
        Ev(trace.START_MARK, 0, 1), Ev(trace.END_MARK, 100, 1)])]),
        Plane("/device:TPU:0", [ops])])
    r = trace.reduce(pd, [("io.write", 20, 80)])
    assert r["idle_gaps"][0][0] == "io.write"


def test_no_marks_or_no_device_op_reads_nothing():
    ops = Line(trace.OPS_LINE, [Ev("%fusion.1 = f", 0, 10)])
    assert trace.reduce(Profile([Plane("/device:TPU:0", [ops])])) is None
    assert trace.reduce(Profile([marks(0, 100)])) is None


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.total([(0, 3), (5, 8)]) == 6
