"""A tiny-size rehearsal of the harness on the CPU: lookup by name, the
loader's window, the metric readers on a recorded run, and whole runs
of tiny cells through the program's `TrainSession`."""
import json
import math
import time

import numpy as np
import pytest

from bench import harness
from bench.loader import UniformTokens, WindowLoader


# ------------------------------------------------------------- lookup

def test_every_cell_of_benchmark_json_is_found_by_name():
    bench = harness.load_benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        wl = harness.load_workload(cell["name"])
        assert wl["config"] == cell["config"]
        assert wl["traffic"] == cell["traffic"]
        assert wl["chips"] == cell["chips"]
        assert wl["why"] == cell["why"]
        conf = harness.load_config(cell["config"])
        assert configs[cell["config"]]["file"] == \
            f"bench/configs/{cell['config']}.json"
        assert sorted(conf["reduced"]) == \
            sorted(configs[cell["config"]]["reduced"])
        for trace in (False, True):
            for m in harness.cell_metrics(bench, cell["name"], trace):
                assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("bad", ["../BENCHMARK", "a/b", "", " x", "x" * 65])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        harness.load_workload(bad)


def test_unknown_name_is_refused():
    with pytest.raises(FileNotFoundError):
        harness.load_config("no-such-config")


def test_cell_metrics_follow_workloads_lists():
    bench = harness.load_benchmark()
    names = lambda cell, trace: {m["name"] for m in  # noqa: E731
                                 harness.cell_metrics(bench, cell, trace)}
    spool, remat = "qwen2.5-3b-4l.spool-1x1024", "qwen2.5-3b-4l.remat-1x4096"
    keep = "qwen2.5-3b-4l.keep-1x1024"
    for cell in (remat, keep):
        assert names(cell, False) == {"tokens_per_s", "mfu", "peak_hbm_gib",
                                      "setup_s"}
        assert names(cell, True) == {
            "host_gap_ms", "step_temp_gib", "device_idle_pct",
            "device_busy_ms", "dispatch_ms", "window_compiles"}
    assert names(spool, False) == {"peak_hbm_gib", "setup_s"}
    assert names(spool, True) == {"step_temp_gib", "spool_gb"}
    assert "spool_gb" not in names(remat, True)
    # every per-layer metric moves an end-to-end metric of its cells
    for cell in (spool, remat, keep):
        e2e = names(cell, False)
        for m in harness.cell_metrics(bench, cell, True):
            assert m["moves"] in e2e, (cell, m["name"])


# ------------------------------------------------------------- loader

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_loader_hands_out_setup_then_stops_at_the_first_boundary_past_seconds():
    clock = FakeClock()
    handed = []
    ld = WindowLoader(UniformTokens(50, 3), batch=2, seq_len=8,
                      setup_steps=3, seconds=1.0, clock=clock,
                      on_handout=handed.append)
    got = [next(ld) for _ in range(3)]
    assert ld.window_start is None and handed == []
    for i in range(4):                     # window batches at t+0..0.9
        got.append(next(ld))
        clock.t += 0.3
    assert ld.window_start == 100.0 and handed == [0, 1, 2, 3]
    with pytest.raises(StopIteration):      # t = 101.2 >= 1 s in
        next(ld)
    assert ld.window_batches == 4
    # batch i is a pure function of (seed, i), and batches differ
    src = UniformTokens(50, 3)
    for i, b in enumerate(got):
        np.testing.assert_array_equal(b["tokens"],
                                      src.batch(i, 2, 8)["tokens"])
    rows = {tuple(r) for b in got for r in b["tokens"]}
    assert len(rows) == 2 * len(got)
    assert np.all(got[0]["labels"][:, :-1] == got[0]["tokens"][:, 1:])
    assert all(0 <= b["tokens"].min() and b["tokens"].max() < 50
               for b in got)


def test_large_seeds_differ_from_their_low_bits():
    import jax
    from bench.weights import seed_key
    a = jax.random.key_data(seed_key(5))
    b = jax.random.key_data(seed_key(5 + (1 << 32)))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    t1 = UniformTokens(100, 2 ** 33 + 1).batch(0, 1, 16)["tokens"]
    t2 = UniformTokens(100, 1).batch(0, 1, 16)["tokens"]
    assert not np.array_equal(t1, t2)


# ------------------------------------------------------ metric readers

def recorded_run(**kw):
    base = dict(
        workload={}, chips=1, setup_s=42.5, window_s=10.0, window_steps=4,
        window_tokens=4096, step_times=[2.0, 2.0, 2.5, 2.5],
        step_ends=[2.5, 4.6, 7.3, 10.0],
        flops_per_token=3.0e9, peak={"bf16_flops_per_s": 197e12},
        peak_bytes_in_use=9 << 30,
        compiled={"argument": 8 << 30, "output": 8 << 30,
                  "alias": 8 << 30, "temp": 3 << 30},
        spool_bytes=8_000_000_000,
        trace={"busy_s": 0.5, "window_s": 2.0, "device_ops": [],
               "idle_gaps": []},
        traced_steps=2)
    base.update(kw)
    return harness.RunRecord(**base)


READ = {
    "tokens_per_s": 409.6,
    "mfu": 100 * 409.6 * 3.0e9 / 197e12,
    "peak_hbm_gib": 11.0,
    "setup_s": 42.5,
    "host_gap_ms": 200.0,
    "step_temp_gib": 3.0,
    "device_idle_pct": 75.0,
    "device_busy_ms": 250.0,
    "spool_gb": 2.0,
}


@pytest.mark.parametrize("name", sorted(READ))
def test_metric_readers_on_a_recorded_run(name):
    got = harness.metric_reader(name)(recorded_run())
    assert got == pytest.approx(READ[name], rel=1e-12)


@pytest.mark.parametrize("name", ["spool_gb", "device_idle_pct",
                                  "device_busy_ms"])
def test_readers_with_nothing_to_read_return_nothing(name):
    run = recorded_run(spool_bytes=None, trace=None, traced_steps=0)
    assert harness.metric_reader(name)(run) is None


def test_host_gap_reads_the_steps_after_the_trace():
    # gaps 0.5, 0.1, 0.2, 0.2 s: untraced, all four; traced for two
    # steps, the trace stops in step 2's gap, so step 3 alone
    read = harness.metric_reader("host_gap_ms")
    assert read(recorded_run(traced_steps=0)) == pytest.approx(250.0)
    assert read(recorded_run(traced_steps=2)) == pytest.approx(200.0)
    assert read(recorded_run(traced_steps=3)) is None


def test_peak_hbm_takes_the_runtime_counter_when_it_is_larger():
    run = recorded_run(peak_bytes_in_use=13 << 30)
    assert harness.metric_reader("peak_hbm_gib")(run) == 13.0


# ------------------------------------------------------- whole tiny runs

@pytest.mark.parametrize("cell,trace", [("tiny.remat", False),
                                        ("tiny.spool", True),
                                        ("tiny_window.remat", False),
                                        ("tiny_moe.remat", False)])
def test_tiny_cell_runs_end_to_end(tiny_bench, cell, trace):
    """Each cell, the test families' (bench/tests/data/families/) too,
    against its own reference."""
    res = harness.run_cell(cell, 2 ** 31 + 7, 1.0, trace,
                           time.perf_counter())
    json.dumps(res)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 1
    if cell.endswith(".remat"):
        assert res["failed"] == 0
    # at these toy step times (~30 ms) the program's spool now and then
    # serves a record it never counted as stored, or fails a load and
    # falls back to recompute (PERF.md, Open questions); the harness
    # counts such a step in `failed`, so the spool cell is not held to 0
    assert 0 <= res["failed"] <= res["attempted"]
    assert list(res)[-1] == "compared"
    bench = json.loads((tiny_bench / "BENCHMARK.json").read_text())
    want = {m["name"] for m in harness.cell_metrics(bench, cell, trace)
            if not m["source"] == "device_trace"}
    assert want <= set(res["metrics"])
    for m in res["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert res["device"]["count"] == 1


# ------------------------------------------------------- failed steps

def test_failed_counts_nan_losses_fallbacks_and_a_broken_partition():
    from types import SimpleNamespace as NS
    from repro.core.spool import SpoolStats
    ok = NS(step=1, loss=1.0, stats=SpoolStats())
    nan = NS(step=2, loss=float("nan"), stats=None)
    fell = NS(step=3, loss=1.0, stats=SpoolStats(fetch_fallbacks=1))
    whole = SpoolStats(num_stores=5, stores_canceled=1, num_loads=4,
                       num_forwarded=2)
    assert harness.count_failed([ok, nan, fell], whole) == 2
    assert harness.count_failed([ok], None) == 0
    lost = SpoolStats(num_stores=4, num_loads=4, num_forwarded=1)
    assert harness.count_failed([ok], lost) == 1
