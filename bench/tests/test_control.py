"""The control and the planted faults, at a tiny size on the CPU: the
plain reference put in the program's place, computed from float8
operands, fed batches with half their tokens left out of the loss, or
serving layer 1's backward the residuals of layer 0, reads not correct
by the cell's comparison. The same readings at the cells' sizes, on the
chip, set the limits (PERF.md)."""
import pytest

from bench import compare, control, harness

SEEDS = (1, 2, 3)


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    from bench.tests import conftest
    mp = pytest.MonkeyPatch()
    try:
        conftest.tiny_bench.__wrapped__(tmp_path_factory.mktemp("c"), mp)
        limits = harness.load_workload("tiny.spool")["limits"]
        yield {s: control.readings("tiny.spool", s) for s in SEEDS}, limits
    finally:
        mp.undo()


@pytest.mark.parametrize("planted", ["control", "half_batch", "residual"])
def test_planted_readings_are_not_correct(readings, planted):
    by_seed, limits = readings
    for seed, got in by_seed.items():
        ok, shown = compare.judge(got[planted], limits)
        assert not ok, (planted, seed, shown)


def test_the_residual_fault_is_planted_only_where_the_spool_is():
    assert "residual" in control.planted_for({"activation_policy": "spool"})
    assert "residual" not in control.planted_for(
        {"activation_policy": "remat"})


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [1.0], "grad_leaf_norms": {"a": 1.0, "b": 2.0},
           "change_leaf_norms": {"a": 0.5, "b": 0.25}}
    stuck = dict(ref, change_leaf_norms={"a": 0.0, "b": 0.0})
    assert compare.numbers(stuck, ref)["change_gap"] == 1.0
