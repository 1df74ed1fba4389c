"""Tests for the repro.obs trace/telemetry subsystem: ring-buffer
exactness (drop counting, incremental cursors), span balance and
per-ring ordering under the HookBridge concurrency stress, the
synthetic-event overlap analyzer, exporter lane duplication, trace
schema validation on garbage input, and a traced end-to-end jit
session (valid Perfetto JSON + per-step obs_* metrics deltas)."""
import dataclasses
import json
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.configs.base import SpoolIoConfig
from repro.configs.paper_models import small_gpt
from repro.core.hooks import HookBridge
from repro.core.spool import ActivationSpool
from repro.io import HostMemoryBackend
from repro.obs import export as obs_export
from repro.obs import overlap as obs_overlap
from repro.obs import tracer as obs_tracer
from repro.obs.tracer import Tracer
from repro.session import TrainSession

MS = 1_000_000          # ns per millisecond, for synthetic events


class _tracer_installed:
    """Install a fresh Tracer as the module tracer for one test, so the
    always-compiled-in call sites record into it; restores whatever was
    there before (normally None) on exit."""

    def __init__(self, ring_size: int = obs_tracer.DEFAULT_RING_SIZE):
        self.tracer = Tracer(ring_size)

    def __enter__(self) -> Tracer:
        self._prev = obs_tracer._TRACER
        obs_tracer._TRACER = self.tracer
        return self.tracer

    def __exit__(self, *exc) -> None:
        obs_tracer._TRACER = self._prev


# ------------------------------------------------------------ ring core

def test_ring_drop_counter_exact():
    """A full ring overwrites oldest events and counts every overwrite:
    dropped == total - capacity, exactly, and the survivors are exactly
    the newest `capacity` events in record order."""
    tr = Tracer(ring_size=8)
    for i in range(20):
        tr.instant(f"ev{i}")
    (ring,) = tr.rings()
    assert ring.total == 20
    assert ring.dropped == 12
    assert tr.dropped() == 12
    assert tr.total_events() == 20
    names = [ev[0] for ev in ring.snapshot()]
    assert names == [f"ev{i}" for i in range(12, 20)]


def test_ring_not_full_drops_nothing():
    tr = Tracer(ring_size=8)
    for i in range(5):
        tr.instant(f"ev{i}")
    (ring,) = tr.rings()
    assert ring.dropped == 0
    assert [ev[0] for ev in ring.snapshot()] == [f"ev{i}"
                                                 for i in range(5)]


def test_incremental_snapshot_cursor():
    """snapshot_new returns only events past the cursor, and composing
    windows loses nothing (while the ring isn't overflowing)."""
    tr = Tracer(ring_size=64)
    for i in range(3):
        tr.instant(f"a{i}")
    first, cur = tr.snapshot_new()
    assert [ev[0] for ev in first] == ["a0", "a1", "a2"]
    for i in range(2):
        tr.instant(f"b{i}")
    second, cur = tr.snapshot_new(cur)
    assert [ev[0] for ev in second] == ["b0", "b1"]
    third, cur = tr.snapshot_new(cur)
    assert third == []


def test_span_recorded_on_exception():
    """A span that raises still records its complete event — the ring
    never ends up with a dangling begin."""
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom", cat="t"):
            raise RuntimeError("x")
    assert tr.open_spans() == 0
    (ev,) = tr.snapshot()
    assert ev[0] == "boom" and ev[3] >= 0


def test_tracer_injectable_clock():
    """Spans and instants read the tracer's injected clock, so tests
    can drive virtual time and assert exact durations regardless of
    machine load (the deflake seam for timing-sensitive asserts)."""
    t = [0]

    def clock():
        t[0] += 5 * MS
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("a", cat="t"):
        pass
    tr.instant("i", cat="t")
    a, i = tr.snapshot()
    assert a[0] == "a" and a[3] == 5 * MS   # exactly one tick inside
    assert i[0] == "i" and i[3] == -1 and i[2] > a[2]


def test_disabled_fast_path_is_noop():
    assert obs_tracer._TRACER is None or True  # doc: default is None
    prev = obs_tracer._TRACER
    obs_tracer._TRACER = None
    try:
        with obs.span("x", cat="t", key=1) as sp:
            sp.set(bytes=3)
        obs.instant("y")
        obs.count("c")
        obs.gauge("g", 1.0)
    finally:
        obs_tracer._TRACER = prev


def test_spans_record_ids_and_their_parent_on_the_thread():
    """Every span gets a unique id and names the span open around it on
    its own thread as `parent`; a span on another thread, or with
    nothing open, has none."""
    tr = Tracer()
    seen = {}

    def other():
        with tr.span("worker", cat="t"):
            pass

    with tr.span("outer", cat="t"):
        outer_id = tr.current_span()
        with tr.span("inner", cat="t"):
            inner_id = tr.current_span()
            t = threading.Thread(target=other)
            t.start()
            t.join()
        with tr.span("sibling", cat="t"):
            pass
    assert tr.current_span() is None
    for name, _, _, _, args in tr.snapshot():
        seen[name] = args
    assert seen["outer"]["id"] == outer_id and "parent" not in seen["outer"]
    assert seen["inner"]["id"] == inner_id
    assert seen["inner"]["parent"] == outer_id
    assert seen["sibling"]["parent"] == outer_id
    assert "parent" not in seen["worker"]
    ids = [a["id"] for a in seen.values()]
    assert len(set(ids)) == len(ids)
    assert tr.open_spans() == 0


def test_current_span_is_none_when_disabled():
    prev = obs_tracer._TRACER
    obs_tracer._TRACER = None
    try:
        assert obs.current_span() is None
    finally:
        obs_tracer._TRACER = prev


def test_spool_jobs_name_the_hook_span_that_caused_them():
    """A store job carries the id of the `hook.offload` that enqueued it
    into the worker's `spool.store` span as `cause`, with the time it
    queued; a backward prefetch's `spool.load` names its `hook.fetch`."""
    arrs = [[np.full((64,), st, np.float32)] for st in range(2)]
    with _tracer_installed() as tr:
        spool = ActivationSpool(HostMemoryBackend(),
                                min_offload_elements=4,
                                store_threads=1, load_threads=1)
        bridge = HookBridge(spool)
        for st in range(2):
            bridge.offload(0, st, arrs[st])
        spool.wait_io()                # stores land: fetches must load
        for st in (1, 0):
            np.testing.assert_array_equal(bridge.fetch(0, st)[0],
                                          arrs[st][0])
        spool.wait_io()
        spool.close()
    events = [ev for ev in tr.snapshot() if ev[3] >= 0]
    by_id = {ev[4]["id"]: ev for ev in events}
    stores = [ev for ev in events if ev[0] == "spool.store"]
    loads = [ev for ev in events if ev[0] == "spool.load"]
    assert len(stores) == 2 and len(loads) == 2
    for ev in stores:
        cause = by_id[ev[4]["cause"]]
        assert cause[0] == "hook.offload"
        assert ev[4]["queued_ms"] >= 0.0
        # the worker started after the enqueuing span did
        assert ev[2] >= cause[2]
    stages = sorted(by_id[ev[4]["cause"]][4]["stage"] for ev in loads)
    assert all(by_id[ev[4]["cause"]][0] == "hook.fetch" for ev in loads)
    # fetch(stage 1) prefetches stage 0 and loads its own record on
    # demand; fetch(stage 0) then waits on the prefetched load
    assert stages == [1, 1]
    # the copy is its own span inside each offload
    copies = [ev for ev in events if ev[0] == "hook.copy"]
    assert len(copies) == 2
    for ev in copies:
        assert by_id[ev[4]["parent"]][0] == "hook.offload"
        assert ev[4]["bytes"] == 256


def test_gauge_samples_are_a_counter_track_that_validates(tmp_path):
    """A gauge is a timestamped sample on the ring, exported as a "C"
    event per sample, so a queue depth reads as a track; the counter
    table keeps the latest value."""
    t = [0]

    def clock():
        t[0] += MS
        return t[0]

    tr = Tracer(clock=clock)
    for depth in (1, 3, 2):
        tr.set_gauge("spool.store_backlog", depth)
    assert tr.counters()["spool.store_backlog"] == 2
    samples = [ev for ev in tr.snapshot() if ev[3] == obs_tracer.GAUGE]
    assert [ev[4]["value"] for ev in samples] == [1, 3, 2]
    track = [ev for ev in obs_export.trace_events(tr)
             if ev["ph"] == "C" and ev["name"] == "spool.store_backlog"]
    assert [ev["args"]["value"] for ev in track] == [1, 3, 2]
    assert [ev["ts"] for ev in track] == sorted(ev["ts"] for ev in track)
    assert track[0]["ts"] > 0
    path = str(tmp_path / "g.json")
    obs_export.write_chrome_trace(path, tr)
    assert obs_export.validate_trace(path) == []
    # the analyzer reads spans only: samples do not count as I/O
    assert obs_overlap.analyze(tr.snapshot())["io_busy_s"] == 0.0
    bad = {"traceEvents": [{"name": "g", "ph": "C", "pid": 0, "tid": 0,
                            "ts": 1.0, "args": {"value": "x"}}]}
    assert obs_export.validate_trace(bad)


# --------------------------------------------- concurrency / integrity

def test_drop_counting_exact_under_threads():
    """N writer threads each push a known number of events into small
    rings; totals and drops must come out exact per ring (each ring is
    appended only by its owner, so no cross-thread races can smear the
    counters)."""
    N_THREADS, N_EVENTS, RING = 6, 500, 64
    tr = Tracer(ring_size=RING)

    def writer(tid):
        for i in range(N_EVENTS):
            tr.instant(f"t{tid}.e{i}", cat="stress")

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rings = tr.rings()
    assert len(rings) == N_THREADS
    for ring in rings:
        assert ring.total == N_EVENTS
        assert ring.dropped == N_EVENTS - RING
        assert len(ring.snapshot()) == RING
    assert tr.total_events() == N_THREADS * N_EVENTS
    assert tr.dropped() == N_THREADS * (N_EVENTS - RING)


def test_trace_integrity_under_hook_bridge_stress():
    """Tracing enabled under the HookBridge shard stress (4 device
    threads x 3 steps x 4 stages racing the spool's store/load
    workers): every span must balance (open_spans == 0 after quiesce),
    per-ring record order must be end-time monotonic (spans are pushed
    at exit), and nothing may drop with a default-sized ring."""
    N_SHARDS, N_STEPS, N_STAGES = 4, 3, 4
    rng = np.random.default_rng(7)
    data = {(s, st, sh): rng.normal(size=(64,)).astype(np.float32)
            for s in range(N_STEPS) for st in range(N_STAGES)
            for sh in range(N_SHARDS)}
    errors = []
    with _tracer_installed() as tr:
        spool = ActivationSpool(HostMemoryBackend(),
                                min_offload_elements=4,
                                store_threads=2, load_threads=2)
        bridge = HookBridge(spool, fetch_timeout=30.0)

        def device_thread(shard):
            try:
                for step in range(N_STEPS):
                    for stage in range(N_STAGES):
                        bridge.offload(step, stage,
                                       [data[(step, stage, shard)]],
                                       shard=shard)
                    for stage in reversed(range(N_STAGES)):
                        out = bridge.fetch(step, stage, shard=shard)
                        np.testing.assert_array_equal(
                            out[0], data[(step, stage, shard)])
            except BaseException as e:   # pragma: no cover - fails test
                errors.append(e)

        threads = [threading.Thread(target=device_thread, args=(sh,))
                   for sh in range(N_SHARDS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spool.wait_io()
        spool.close()
    assert not errors, errors

    # every begin had a matching end, on every thread
    assert tr.open_spans() == 0
    assert tr.dropped() == 0
    assert tr.total_events() > 0
    # record order == push order == span-end order, per ring: the end
    # timestamp (ts + dur, or ts for instants) must never go backwards
    for ring in tr.rings():
        ends = [ts + max(dur, 0) for _, _, ts, dur, _ in ring.snapshot()]
        assert ends == sorted(ends), ring.thread_name
    # the hook layer traced every offload/fetch exactly once
    events = tr.snapshot()
    names = [ev[0] for ev in events]
    total = N_SHARDS * N_STEPS * N_STAGES
    assert names.count("hook.offload") == total
    assert names.count("hook.fetch") == total
    # the bridge prefetches one module ahead on the backward path; a
    # hint only counts as issued when it starts a real backend load
    # (in-flight stores forward instead), and every resolved hint is a
    # hit or a late — never both — so resolutions can't exceed issues
    c = tr.counters()
    assert (c.get("prefetch.hit", 0) + c.get("prefetch.late", 0)
            <= c.get("prefetch.issued", 0))


def test_prefetch_counters_deterministic():
    """Drive the spool's prefetch counters through every outcome with
    barriers so the result is deterministic: a hint against a completed
    store issues a load (issued); fetching after the load lands is a
    hit; a prefetched load that is dropped unconsumed is a ghost."""
    rng = np.random.default_rng(0)
    # distinct payloads per stage, or dedup aliases them to one record
    arrs = {st: [rng.normal(size=(64,)).astype(np.float32)]
            for st in (0, 1)}
    with _tracer_installed() as tr:
        spool = ActivationSpool(HostMemoryBackend(),
                                min_offload_elements=4)
        with spool.step("s0") as tx:
            tx.offload(0, arrs[0])
            tx.offload(1, arrs[1])
            spool.wait_io()          # stores done: hints start real loads
            tx.prefetch(0)
            tx.prefetch(1)
            spool.wait_io()          # loads done: the fetch is a hit
            out = tx.fetch(0)
            np.testing.assert_array_equal(out[0], arrs[0][0])
            # stage 1's prefetched load is never fetched: the lease
            # drop on __exit__ makes it a ghost
        spool.close()
    c = tr.counters()
    assert c.get("prefetch.issued", 0) == 2
    assert c.get("prefetch.hit", 0) == 1
    assert c.get("prefetch.late", 0) == 0
    assert c.get("prefetch.ghost", 0) == 1


# ------------------------------------------------------ overlap analyzer

def _span_ev(name, lo_ms, hi_ms, key=None, cat="t"):
    args = {} if key is None else {"key": key}
    return (name, cat, lo_ms * MS, (hi_ms - lo_ms) * MS, args)


def test_overlap_analyzer_synthetic():
    """Hand-built timeline with known numbers: 20 ms of I/O, 7 ms of
    exposed wait (5 overlapping the same key's disk read, 1 its decode,
    1 queued), so hidden = 1 - 7/20 = 0.65."""
    events = [
        _span_ev("io.read", 0, 10, key="a"),
        _span_ev("spool.fetch_wait", 5, 12, key="a"),
        _span_ev("codec.decode", 10, 11, key="a"),
        _span_ev("io.write", 20, 30, key="b"),
        _span_ev("codec.encode", 18, 20, key="b"),
        ("spool.offload", "spool", 1 * MS, -1, {}),   # instant: ignored
    ]
    res = obs_overlap.analyze(events, {"prefetch.issued": 4,
                                       "prefetch.hit": 3,
                                       "prefetch.late": 1})
    assert res["io_busy_s"] == pytest.approx(0.020)
    assert res["exposed_wait_s"] == pytest.approx(0.007)
    assert res["io_hidden_frac"] == pytest.approx(0.65)
    assert res["stall_read_s"] == pytest.approx(0.005)
    assert res["stall_decode_s"] == pytest.approx(0.001)
    assert res["stall_queue_s"] == pytest.approx(0.001)
    assert res["encode_s"] == pytest.approx(0.002)
    assert res["prefetch_hit_rate"] == pytest.approx(0.75)


def test_overlap_analyzer_interval_union():
    """Overlapping spans of the same kind are unioned, not summed —
    two concurrent 10 ms reads on [0,10) are 10 ms of I/O, not 20."""
    events = [_span_ev("io.read", 0, 10, key="a"),
              _span_ev("io.read", 0, 10, key="b")]
    res = obs_overlap.analyze(events)
    assert res["io_busy_s"] == pytest.approx(0.010)
    assert res["io_hidden_frac"] == 1.0


def test_overlap_analyzer_empty_window():
    res = obs_overlap.analyze([])
    assert res["io_busy_s"] == 0.0
    assert res["io_hidden_frac"] == 1.0   # no I/O, nothing exposed


def test_overlap_analyzer_opt_attribution():
    """Opt-keyed spans (the opt-overlap bridge's moment leases) leave
    the activation metrics and land in the opt lane; only the training
    thread's spans count as exposed — the side worker blocking on its
    own reads is the hidden case — and a thread block is charged to the
    I/O hidden fraction only where it intersects opt I/O activity (the
    rest of a join is the worker's update compute, not I/O)."""
    events = [
        _span_ev("io.read", 0, 10, key="act0"),             # activation
        _span_ev("io.read", 0, 8, key="opt3L1"),            # moment fetch
        _span_ev("spool.fetch_wait", 0, 8, key="opt3L1"),   # worker wait
        _span_ev("io.write", 20, 26, key="opt4L1"),         # moment stage
        _span_ev("engine.opt_join", 24, 32),                # exposed join
    ]
    res = obs_overlap.analyze(events)
    assert res["io_busy_s"] == pytest.approx(0.010)     # activation only
    assert res["exposed_wait_s"] == 0.0                 # opt wait is not
    assert res["opt_io_busy_s"] == pytest.approx(0.014)   # [0,8)+[20,26)
    assert res["opt_exposed_wait_s"] == pytest.approx(0.008)
    # the join [24,32) overlaps opt I/O only on [24,26); the other 6 ms
    # rode out the worker's update kernels — compute, not I/O
    assert res["opt_exposed_io_s"] == pytest.approx(0.002)
    assert res["opt_hidden_frac"] == pytest.approx(1.0 - 2.0 / 14.0)
    # serial staging (engine.opt_fetch/opt_stage wrap the spool calls):
    # busy is fully covered by exposed, so nothing is hidden
    serial = obs_overlap.analyze([
        _span_ev("io.read", 0, 8, key="opt3"),
        _span_ev("engine.opt_fetch", 0, 9),
        _span_ev("io.write", 10, 16, key="opt4"),
        _span_ev("engine.opt_stage", 10, 17),
    ])
    assert serial["opt_io_busy_s"] == pytest.approx(0.014)
    assert serial["opt_hidden_frac"] == pytest.approx(0.0)


def test_predicted_vs_measured_pairing():
    from repro.launch.dryrun import _predict_overlap
    pred = _predict_overlap(1e9, 3e9, 3.0)   # fits both windows
    assert pred["io_hidden_frac"] == 1.0
    paired = obs_overlap.predicted_vs_measured(
        pred, {"io_busy_s": 0.6, "io_hidden_frac": 0.9})
    assert paired["predicted_io_s"] == pytest.approx(2 / 3)
    assert paired["hidden_frac_error"] == pytest.approx(-0.1)
    # saturated store path: writes take 3x the fwd window
    slow = _predict_overlap(9e9, 1e9, 3.0)
    assert slow["io_hidden_frac"] < 1.0
    assert slow["exposed_wait_s"] == pytest.approx(
        (9.0 - 1.0) + (9.0 - 2.0))


# --------------------------------------------------- export + validation

def test_exporter_duplicates_shard_and_tier_lanes():
    tr = Tracer()
    with tr.span("hook.offload", cat="hook", args={"shard": 2}):
        pass
    with tr.span("io.write", cat="io", args={"kind": "mem", "key": "k"}):
        pass
    tr.instant("plain", cat="t")
    events = obs_export.trace_events(tr)
    by_pid = {}
    for ev in events:
        if ev["ph"] in ("X", "i"):
            by_pid.setdefault(ev["pid"], []).append(ev["name"])
    assert "hook.offload" in by_pid[obs_export.PID_THREADS]
    assert by_pid[obs_export.PID_SHARDS] == ["hook.offload"]
    assert by_pid[obs_export.PID_TIERS] == ["io.write"]
    # lane metadata names the shard / backend kind
    meta = {(ev["pid"], ev["tid"]): ev["args"]["name"]
            for ev in events if ev["ph"] == "M"
            and ev["name"] == "thread_name"}
    assert meta[(obs_export.PID_SHARDS, 0)] == "shard 2"
    assert meta[(obs_export.PID_TIERS, 0)] == "tier mem"


def test_validate_trace_accepts_exporter_output(tmp_path):
    tr = Tracer()
    with tr.span("io.write", cat="io", args={"kind": "mem"}):
        pass
    path = str(tmp_path / "t.json")
    obs_export.write_chrome_trace(path, tr, extra={"engine": "test"})
    assert obs_export.validate_trace(path, expect_cats=("io",)) == []
    doc = json.load(open(path))
    assert doc["otherData"]["engine"] == "test"
    assert doc["otherData"]["open_spans"] == 0


def test_validate_trace_rejects_garbage(tmp_path):
    assert obs_export.validate_trace({"nope": 1})
    assert obs_export.validate_trace({"traceEvents": "not-a-list"})
    errors = obs_export.validate_trace({"traceEvents": [
        {"ph": "X", "pid": 0, "tid": 0, "ts": 1.0},          # no name/dur
        {"name": "n", "ph": "Z", "pid": 0, "tid": 0, "ts": 0},  # bad ph
        {"name": "n", "ph": "X", "pid": 0, "tid": 0, "ts": 0,
         "dur": -5},                                         # bad dur
        "not an object",
    ]})
    assert len(errors) >= 4
    # expected-category enforcement
    errors = obs_export.validate_trace(
        {"traceEvents": [{"name": "n", "ph": "i", "cat": "spool",
                          "pid": 0, "tid": 0, "ts": 0, "s": "t"}]},
        expect_cats=("spool", "io"))
    assert any("'io'" in e for e in errors)
    # unreadable path
    assert obs_export.validate_trace(str(tmp_path / "missing.json"))


# --------------------------------------------------- end-to-end session

def test_traced_jit_session_end_to_end(tmp_path):
    """--trace on the jit engine with activation offload: the session
    writes a schema-valid Perfetto trace covering spool/io/codec/
    engine/hook, and each JSONL row carries its own step's deltas —
    obs_* overlap fields, per-shard traffic, and non-cumulative spool
    byte counts."""
    trace_path = str(tmp_path / "trace.json")
    metrics_path = str(tmp_path / "metrics.jsonl")
    cfg = dataclasses.replace(small_gpt(128, 2), dtype="float32")
    io = SpoolIoConfig(backend="mem", host_offload="activations")
    with TrainSession(cfg, engine="jit", io=io, optimizer="sgd",
                      lr=1e-3, batch_size=2, seq_len=32, seed=0,
                      ckpt_every=0, min_offload_elements=2 ** 8,
                      metrics_path=metrics_path,
                      trace=trace_path) as sess:
        result = sess.run(3)
        sess.spool.wait_io()
        total_offloaded = sess.spool.stats.bytes_offloaded
    assert obs_tracer._TRACER is None    # session-owned tracer released

    assert obs_export.validate_trace(
        trace_path,
        expect_cats=("spool", "io", "codec", "engine", "hook")) == []
    doc = json.load(open(trace_path))
    assert doc["otherData"]["open_spans"] == 0
    assert doc["otherData"]["dropped_events"] == 0

    rows = [json.loads(l) for l in open(metrics_path)]
    assert len(rows) == 3
    for row in rows:
        assert row["bytes_offloaded"] >= 0
        assert 0.0 <= row["obs_io_hidden_frac"] <= 1.0
        assert row["obs_io_busy_s"] > 0
        assert row["shards"]["global"]["offloads"] > 0
    # per-step deltas, not cumulative — but stores are async, so under
    # load a slow store can land in the NEXT step's delta window.
    # Assert conservation (the row deltas sum to the run's total spool
    # traffic) instead of pinning identical per-row byte counts.
    offl = [row["bytes_offloaded"] for row in rows]
    assert sum(offl) > 0
    assert sum(offl) <= total_offloaded, (offl, total_offloaded)
    assert [r.obs for r in result.reports] is not None
    last = result.reports[-1].obs
    assert last["prefetch_issued"] >= last["prefetch_hit"]
