"""Tests for the jit engine's per-layer activation offloading
(repro.core.hooks): correctness vs the no-offload baseline, tensor
forwarding under an io_callback fetch racing the store, one
AdaptivePolicy profile driving both engines, the staged engine's
backward-prefetch off-by-one regression, and the SPMD bridge
machinery — shard planning, per-shard lease keying under concurrent
host-callback threads, and the replica-countdown consume protocol."""
import dataclasses
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import SpoolIoConfig
from repro.configs.paper_models import small_gpt
from repro.core.hooks import HookBridge, plan_shards, run_splits
from repro.core.policies import (AdaptivePolicy, JitOffloadPlan,
                                 SpoolPolicy, local_shard_fraction)
from repro.core.spool import ActivationSpool, SpoolStepTransaction
from repro.core.staged import StagedTrainer
from repro.io import FilesystemBackend, HostMemoryBackend
from repro.models.transformer import RunSettings
from repro.session import TrainSession

MIN_OFF = 2 ** 8


def _cfg(hidden=128, layers=2):
    return dataclasses.replace(small_gpt(hidden, layers), dtype="float32")


def _session(engine, **kw):
    kw.setdefault("optimizer", "adamw")
    kw.setdefault("lr", 1e-3)
    kw.setdefault("batch_size", 2)
    kw.setdefault("seq_len", 32)
    kw.setdefault("seed", 3)
    kw.setdefault("ckpt_every", 0)
    kw.setdefault("min_offload_elements", MIN_OFF)
    return TrainSession(_cfg(), engine=engine, **kw)


def _keep_settings():
    return RunSettings(attn_impl="xla", attn_chunk=256,
                       activation_policy="keep", param_dtype="float32")


# ------------------------------------------------- jit activations mode

@pytest.fixture(scope="module")
def jit_baseline():
    """No-offload jit run (residuals kept on device): 3 steps."""
    with _session("jit", settings=_keep_settings()) as sess:
        result = sess.run(3)
        return {"losses": result.losses, "params": result.state.params}


@pytest.fixture(scope="module")
def hooked_baseline():
    """SAME-COMPILE bitwise reference for the activation-offload path.

    The hooked step is a different XLA program than the keep-settings
    one (the io_callbacks change fusion decisions in the backward), so
    comparing hooked losses against `jit_baseline` bitwise is comparing
    two compiles — after the first optimizer update the params carry
    ~1-ulp fusion noise and step>=1 losses legitimately differ in the
    last bit (the old flaky assertion). The invariant offloading must
    actually guarantee is *placement transparency*: the same compiled
    program must produce bitwise-identical results no matter which
    backend holds the residuals or how stores race fetches. This mem-
    backend hooked run is the reference for that comparison."""
    with _session("jit", io=SpoolIoConfig(
            backend="mem", host_offload="activations")) as sess:
        result = sess.run(3)
        return {"losses": result.losses, "params": result.state.params,
                "reports": result.reports}


def test_jit_activations_matches_no_offload_baseline(jit_baseline,
                                                     hooked_baseline):
    """host_offload="activations" must be math-transparent: bitwise
    equal to the same-compile hooked reference across backends
    (placement transparency), equal to the no-offload jit baseline up
    to cross-compile fusion noise, and real residual bytes must land on
    the backend."""
    with _session("jit", io=SpoolIoConfig(
            backend="fs", host_offload="activations")) as sess:
        result = sess.run(3)
        stats = dataclasses.replace(sess.spool.stats)
        io_writes = sess.spool.backend.stats.num_writes
        leftover = dict(sess.spool._records)
    # same compiled program, different residual placement: bitwise
    assert result.losses == hooked_baseline["losses"]
    for a, b in zip(jax.tree.leaves(hooked_baseline["params"]),
                    jax.tree.leaves(result.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # vs the keep-settings compile: NOT asserted bitwise — a different
    # XLA program fuses the backward differently, so updated params
    # (and every loss computed from them) may differ in the last ulp.
    # The tolerance covers that compile noise, nothing more.
    np.testing.assert_allclose(result.losses, jit_baseline["losses"],
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(jit_baseline["params"]),
                    jax.tree.leaves(result.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    # per-segment residuals really landed on the configured backend
    assert stats.bytes_offloaded > 0
    assert io_writes > 0
    assert stats.num_stores > 0
    # every step lease was consumed: no records strand on the spool
    assert not leftover


def test_hook_seconds_reach_shard_stats_per_step(hooked_baseline):
    """The bridge's always-on callback clocks — `copy_s` inside
    `offload_s`, and `fetch_s` — arrive in every step's `shard_stats`
    as per-step deltas beside the byte counters."""
    for rep in hooked_baseline["reports"]:
        g = rep.shard_stats["global"]
        assert g["offloads"] > 0 and g["fetches"] == g["offloads"]
        assert 0.0 < g["copy_s"] <= g["offload_s"]
        assert g["fetch_s"] > 0.0
        # a delta, not the run so far: no step holds more than the
        # wall time of its own step
        assert g["offload_s"] + g["fetch_s"] <= rep.step_time
        assert rep.to_metrics()["shards"]["global"]["copy_s"] == \
            g["copy_s"]


def test_bridge_times_copy_inside_offload():
    spool = ActivationSpool(HostMemoryBackend(), min_offload_elements=4,
                            store_threads=1, load_threads=1)
    bridge = HookBridge(spool)
    arrays = [np.ones((1 << 20,), np.float32)]       # 4 MB to copy
    bridge.offload(0, 0, arrays)
    after_offload = bridge.stats_by_shard()[None]
    assert 0.0 < after_offload["copy_s"] <= after_offload["offload_s"]
    assert after_offload["fetch_s"] == 0.0
    np.testing.assert_array_equal(bridge.fetch(0, 0)[0], arrays[0])
    rec = bridge.stats_by_shard()[None]
    assert rec["fetch_s"] > 0.0
    assert rec["copy_s"] == after_offload["copy_s"]
    assert rec["bytes_in"] == rec["bytes_out"] == arrays[0].nbytes
    spool.close()


def test_jit_vs_staged_parity_with_activations():
    """Same arch/seed through one front door: the staged (TBA) engine
    and the jit engine with per-layer activation offloading train to
    matching losses."""
    with _session("staged") as sess:
        staged = sess.run(3).losses
    with _session("jit", io=SpoolIoConfig(
            backend="mem", host_offload="activations")) as sess:
        hooked = sess.run(3).losses
    assert np.all(np.isfinite(staged)) and np.all(np.isfinite(hooked))
    np.testing.assert_allclose(staged, hooked, rtol=5e-3)


def test_forwarding_under_fetch_racing_store(hooked_baseline):
    """A backward io_callback fetch that catches the store still queued
    or in flight must forward the in-memory reference (§3.3.2) — and
    the math stays exact either way: bitwise against the same-compile
    hooked reference (see `hooked_baseline` for why not the keep one)."""
    with _session("jit", io=SpoolIoConfig(
            backend="fs", store_threads=1, bandwidth_limit=2e6,
            host_offload="activations")) as sess:
        result = sess.run(2)
        stats = dataclasses.replace(sess.spool.stats)
    assert stats.bytes_forwarded > 0
    assert result.losses == hooked_baseline["losses"][:2]  # bitwise


def test_activations_mode_cli_flag_roundtrip():
    io = SpoolIoConfig(backend="mem", host_offload="activations")
    assert io.validate() is io
    with pytest.raises(AssertionError):
        SpoolIoConfig(host_offload="everything").validate()


def test_activations_with_non_spool_settings_rejected():
    """host_offload="activations" + explicit settings that never engage
    the hooks must raise, not silently train with zero offload."""
    with pytest.raises(ValueError, match="activation_policy"):
        TrainSession(_cfg(), engine="jit", settings=_keep_settings(),
                     io=SpoolIoConfig(backend="mem",
                                      host_offload="activations"))


def test_encdec_spools_encoder_and_decoder_residuals():
    """Cross-attention segments close over the encoder states; the
    hooks must thread them as an explicit custom_vjp input (carry) or
    trace-time differentiation fails — and both streams' residuals
    should hit the backend."""
    from repro.configs.paper_models import small_t5
    cfg = dataclasses.replace(small_t5(), dtype="float32")
    rng = np.random.default_rng(0)

    def batches():
        return [{"tokens": rng.integers(0, 100, (2, 16)),
                 "enc_tokens": rng.integers(0, 100, (2, 16)),
                 "labels": rng.integers(0, 100, (2, 16))}
                for _ in range(2)]

    with TrainSession(cfg, engine="jit", seed=0, ckpt_every=0,
                      loader=batches(), min_offload_elements=2 ** 6,
                      io=SpoolIoConfig(backend="mem",
                                       host_offload="activations")) as s:
        hooked = s.run(2)
        stats = dataclasses.replace(s.spool.stats)
    assert np.all(np.isfinite(hooked.losses))
    assert stats.num_stores > 0
    rng = np.random.default_rng(0)       # same batch stream
    with TrainSession(cfg, engine="jit", seed=0, ckpt_every=0,
                      loader=batches(),
                      settings=RunSettings(
                          attn_impl="xla", attn_chunk=256,
                          activation_policy="keep",
                          param_dtype="float32")) as s:
        base = s.run(2)
    np.testing.assert_allclose(hooked.losses, base.losses, rtol=1e-5)


# --------------------------------------- one policy, both engines

def test_adaptive_plan_drives_both_engines():
    """Profile once on the staged engine, then translate the same plan
    into jit RunSettings via plan_for_jit()."""
    pol = AdaptivePolicy()
    with pytest.raises(RuntimeError):
        pol.plan_for_jit()          # no profile digested yet
    with _session("staged", policy=pol) as sess:
        staged_losses = sess.run(2).losses
    assert pol.plan is not None
    jplan = pol.plan_for_jit()
    assert isinstance(jplan, JitOffloadPlan)
    assert len(jplan.spool_stages) == 2          # one entry per layer
    assert jplan.write_bw == pol.plan.write_bw

    settings = jplan.apply(_keep_settings())
    if jplan.activation_policy == "spool":
        assert settings.spool_stages == jplan.spool_stages
        with _session("jit", settings=settings, io=SpoolIoConfig(
                backend="mem", host_offload="activations")) as sess:
            jit_losses = sess.run(2).losses
            assert sess.spool.stats.num_stores > 0
    else:                            # plan kept everything on device
        assert settings.spool_stages is None
        with _session("jit", settings=settings) as sess:
            jit_losses = sess.run(2).losses
    assert np.all(np.isfinite(staged_losses))
    np.testing.assert_allclose(staged_losses, jit_losses, rtol=5e-3)


def test_run_splits_groups_contiguous_choices():
    assert run_splits([True, True, False, True]) == [
        (0, 2, True), (2, 3, False), (3, 4, True)]
    assert run_splits([False, False]) == [(0, 2, False)]
    assert run_splits([]) == []


def test_partial_spool_stages_mask():
    """A mixed keep/offload plan splits the scanned stack but must not
    change the math."""
    settings = dataclasses.replace(
        _keep_settings(), activation_policy="spool",
        spool_stages=(True, False))
    with _session("jit", settings=settings, io=SpoolIoConfig(
            backend="mem", host_offload="activations")) as sess:
        masked = sess.run(2)
        stats = dataclasses.replace(sess.spool.stats)
    with _session("jit", settings=_keep_settings()) as sess:
        base = sess.run(2)
    assert masked.losses == base.losses            # bitwise
    assert stats.num_stores > 0                    # layer 0 still spools


# ----------------------------------------- SPMD bridge machinery

def _mesh_or_skip(shape, names):
    if jax.device_count() < int(np.prod(shape)):
        pytest.skip("needs forced host devices")
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(shape, names)


def test_plan_shards_specs_and_replica_factorization():
    """Leaf spec choice: leading dim over dp when divisible, innermost
    other divisible dim over tp; axes sharding nothing become replica
    axes (their devices hold identical bytes)."""
    from jax.sharding import PartitionSpec as P
    mesh = _mesh_or_skip((1,), ("data",))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}

    sds = [jax.ShapeDtypeStruct((8, 32, 128), np.float32),  # dp + tp
           jax.ShapeDtypeStruct((8, 32, 3), np.float32),    # tp on seq
           jax.ShapeDtypeStruct((8, 3, 3), np.float32),     # dp only
           jax.ShapeDtypeStruct((), np.float32)]            # scalar
    plan = plan_shards(FakeMesh(), ("data",), "model", sds)
    assert plan.specs[0] == P("data", None, "model")
    assert plan.specs[1] == P("data", "model", None)   # innermost
    assert plan.specs[2] == P("data", None, None)      # divisible dim
    assert plan.specs[3] == P()
    assert plan.writer_axes == ("data", "model")
    assert plan.replica_axes == ()
    assert plan.n_shards == 8 and plan.n_replicas == 1
    local = plan.local_sds(sds)
    assert local[0].shape == (4, 32, 32)
    assert local[1].shape == (4, 8, 3)
    assert local[2].shape == (4, 3, 3)

    # batch indivisible by dp, no tp -> nothing shards, whole mesh is
    # one replica group
    plan2 = plan_shards(FakeMesh(), ("data",), None,
                        [jax.ShapeDtypeStruct((3, 5), np.float32)])
    assert plan2.writer_axes == ()
    assert plan2.replica_axes == ("data", "model")
    assert plan2.n_shards == 1 and plan2.n_replicas == 8


def test_local_shard_fraction_and_scaled_jit_plan():
    """plan_for_jit(shard_fraction=...) re-plans against the LOCAL
    per-shard byte volume: a smaller fraction can only offload more
    layers, and the planned required_bw scales with the bytes."""
    from repro.core.adaptive import ModuleProfile

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    assert local_shard_fraction(None) == 1.0
    assert local_shard_fraction(FakeMesh(), ("data",)) == 0.25

    pol = AdaptivePolicy()
    profiles = [ModuleProfile(f"seg0_l{i}", 100 << 20, 0.01)
                for i in range(6)]
    pol.on_profile(profiles, 2.0e9)      # tight scalar bandwidth
    full = pol.plan_for_jit()
    quarter = pol.plan_for_jit(shard_fraction=0.25)
    assert len(quarter.spool_stages) == len(full.spool_stages) == 6
    assert sum(quarter.spool_stages) >= sum(full.spool_stages)
    assert quarter.shard_fraction == 0.25
    assert quarter.required_bw < full.required_bw or \
        sum(quarter.spool_stages) > sum(full.spool_stages)
    with pytest.raises(ValueError):
        pol.plan_for_jit(shard_fraction=0.0)


def test_bridge_replica_countdown_consume():
    """Satellite fix: a stage fetched once per replica shard is dropped
    by the LAST fetch only — earlier fetches peek (non-consuming), and
    the lease closes once every stage of that shard is consumed."""
    spool = ActivationSpool(HostMemoryBackend(), min_offload_elements=4,
                            store_threads=1, load_threads=1)
    bridge = HookBridge(spool, fetch_timeout=5.0)
    rng = np.random.default_rng(0)
    arrays = [rng.normal(size=(256,)).astype(np.float32)]
    bridge.sharded_offload(0, 0, arrays, shard=0, replica=0,
                           n_replicas=3)
    bridge.sharded_offload(0, 0, arrays, shard=0, replica=1,
                           n_replicas=3)     # dedupe: skipped
    spool.wait_io()
    for rep in range(3):
        out = bridge.sharded_fetch(0, 0, shard=0, replica=rep,
                                   n_replicas=3)
        np.testing.assert_array_equal(out[0], arrays[0])
        live = bridge._txs.get("jit0/s0")
        if rep < 2:
            assert live is not None and live.has_stage(0)
        else:
            assert live is None          # last consumer closed the lease
    assert not spool._records
    stats = bridge.stats_by_shard()[0]
    assert stats["offloads"] == 1 and stats["replica_skips"] == 1
    assert stats["fetches"] == 3
    # a 4th fetch of the consumed stage is an error, not a hang
    bridge.fetch_timeout = 0.2
    with pytest.raises(KeyError):
        bridge.sharded_fetch(0, 0, shard=0, replica=0, n_replicas=3)
    spool.close()


def test_bridge_dedupe_disabled_stores_per_replica():
    spool = ActivationSpool(HostMemoryBackend(), min_offload_elements=4,
                            store_threads=1, load_threads=1)
    bridge = HookBridge(spool, dedupe_replicas=False, fetch_timeout=5.0)
    rng = np.random.default_rng(1)
    for rep in range(2):
        bridge.sharded_offload(0, 0, [rng.normal(size=(64,))
                                      .astype(np.float32)],
                               shard=1, replica=rep, n_replicas=2)
    spool.wait_io()
    assert spool.stats.num_stores == 2   # one blob per replica
    for rep in range(2):
        bridge.sharded_fetch(0, 0, shard=1, replica=rep, n_replicas=2)
    assert not bridge._txs and not spool._records
    spool.close()


def test_bridge_fetch_waits_for_late_offload():
    """On a mesh the fetch and store callbacks arrive on different
    threads; a fetch that beats its store must WAIT, not fail."""
    spool = ActivationSpool(HostMemoryBackend(), min_offload_elements=4,
                            store_threads=1, load_threads=1)
    bridge = HookBridge(spool, fetch_timeout=10.0)
    arr = np.arange(64, dtype=np.float32)

    def late_offload():
        time.sleep(0.3)
        bridge.offload(7, 0, [arr], shard=2)

    t = threading.Thread(target=late_offload)
    t.start()
    out = bridge.fetch(7, 0, shard=2)    # arrives first, waits
    t.join()
    np.testing.assert_array_equal(out[0], arr)
    assert not bridge._txs
    spool.close()


def test_hook_bridge_concurrent_shard_stress():
    """Satellite: hammer offload/fetch from N threads emulating XLA
    host-callback workers across interleaved steps. No cross-step key
    leaks, and SpoolStats counters sum EXACTLY: every record's bytes
    are either forwarded (store still in flight / cancelled) or loaded
    back — never both, never neither."""
    N_SHARDS, N_STEPS, N_STAGES = 4, 3, 4
    spool = ActivationSpool(HostMemoryBackend(), min_offload_elements=4,
                            store_threads=2, load_threads=2)
    bridge = HookBridge(spool, fetch_timeout=30.0)
    rng = np.random.default_rng(2)
    # unique payloads (no dedup aliasing) sized well over the threshold
    data = {(s, st, sh): rng.normal(size=(512,)).astype(np.float32)
            for s in range(N_STEPS) for st in range(N_STAGES)
            for sh in range(N_SHARDS)}
    errors = []

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
        except BaseException as e:       # pragma: no cover - fails test
            errors.append(e)

    threads = [threading.Thread(target=device_thread, args=(sh,))
               for sh in range(N_SHARDS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spool.wait_io()
    assert not errors, errors
    # no cross-step leaks: every lease closed, no records, no step ids
    assert not bridge._txs
    assert not spool._records
    assert not spool._active_steps
    # exact accounting
    total = N_SHARDS * N_STEPS * N_STAGES
    total_bytes = sum(a.nbytes for a in data.values())
    by_shard = bridge.stats_by_shard()
    assert sorted(by_shard) == list(range(N_SHARDS))
    assert sum(v["offloads"] for v in by_shard.values()) == total
    assert sum(v["fetches"] for v in by_shard.values()) == total
    assert sum(v["bytes_in"] for v in by_shard.values()) == total_bytes
    assert sum(v["bytes_out"] for v in by_shard.values()) == total_bytes
    st = spool.stats
    per_rec = data[(0, 0, 0)].nbytes     # uniform record size
    # every offload enqueued exactly one store job; each completed or
    # was cancelled by a forwarding fetch
    assert st.num_stores + st.stores_canceled == total
    # every fetch either forwarded the in-flight arrays or reloaded the
    # blob — exactly once per record, partitioning the byte volume
    assert st.bytes_forwarded % per_rec == 0
    n_fwd = st.bytes_forwarded // per_rec
    assert st.num_loads == total - n_fwd
    # completed stores wrote exactly their logical bytes (+ the serde
    # container, identical per record); loads read the same blobs back
    assert st.bytes_offloaded_logical == st.num_stores * per_rec
    if st.num_stores:
        encoded_per_rec = st.bytes_offloaded // st.num_stores
        assert st.bytes_offloaded == st.num_stores * encoded_per_rec
        assert st.bytes_loaded == st.num_loads * encoded_per_rec
    spool.close()


# ------------------------------------- staged backward-prefetch fix

class _SlowReadBackend(FilesystemBackend):
    """Filesystem backend whose reads take `delay` seconds — makes the
    cost of a cold (non-prefetched) load deterministic."""

    def __init__(self, directory, delay):
        super().__init__(directory)
        self.delay = delay

    def read(self, key):
        time.sleep(self.delay)
        return super().read(key)

    def readinto(self, key, buf):
        # the pooled data plane loads through readinto, not read
        time.sleep(self.delay)
        return super().readinto(key, buf)


def _staged_wait(delay, monkeypatch, *, simulate_bug):
    from repro.models.api import build_model
    from repro.optim.optimizers import sgd

    if simulate_bug:
        orig = SpoolStepTransaction.prefetch

        def skip_stage0(self, stage):
            if stage == 0:
                return              # the old `si - 1 > 0` behavior
            orig(self, stage)

        monkeypatch.setattr(SpoolStepTransaction, "prefetch", skip_stage0)
    api = build_model(_cfg(128, 2))
    settings = RunSettings(attn_impl="xla", attn_chunk=32,
                           param_dtype="float32")
    backend = _SlowReadBackend(tempfile.mkdtemp(prefix="slow_spool_"),
                               delay)
    # threshold low enough that the embed stage's residuals (the token
    # indices) spool too — stage 0 is the stage the off-by-one skipped
    tr = StagedTrainer(api, settings, sgd(1e-2), policy=SpoolPolicy(),
                       backend=backend, min_offload_elements=16)
    try:
        params = api.init(jax.random.key(0))
        opt_state = tr.optimizer.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, 100, (2, 32)),
                 "labels": rng.integers(0, 100, (2, 32))}
        _, _, rep = tr.train_step(params, opt_state, [batch])
        assert np.isfinite(rep.loss)
        # bytes_forwarded > 0 means a fetch was served from a store
        # still in flight — the cold read this helper exists to time
        # never happened, so the caller must discard the measurement
        return (tr.spool.stats.fetch_wait_time,
                tr.spool.stats.bytes_forwarded)
    finally:
        monkeypatch.undo()
        tr.close()


def test_backward_prefetch_covers_stage0(monkeypatch):
    """Regression for the `si - 1 > 0` off-by-one: stage 0 (embed) must
    be prefetched one module ahead like every other stage, so its fetch
    no longer pays a cold blocking load — fetch_wait_time drops by about
    one full read delay vs the buggy behavior."""
    prefetched = []
    orig = SpoolStepTransaction.prefetch

    def spy(self, stage):
        prefetched.append(stage)
        orig(self, stage)

    monkeypatch.setattr(SpoolStepTransaction, "prefetch", spy)
    delay = 0.2
    fixed_wait, _ = _staged_wait(delay, monkeypatch, simulate_bug=False)
    assert 0 in prefetched          # embed stage now prefetched
    # The timing comparison is only meaningful when the buggy run
    # actually pays the cold read: if the backward reaches stage 0
    # while its store is still in flight, fetch forwards the arrays
    # from memory (bytes_forwarded > 0) and no cold load happens at
    # all. That race is load-dependent, so retry until a run pays it.
    for _ in range(3):
        buggy_wait, buggy_fwd = _staged_wait(delay, monkeypatch,
                                             simulate_bug=True)
        if buggy_fwd == 0:
            break
    else:
        pytest.skip("stage-0 store raced every attempt: the cold-read "
                    "path cannot be exercised under this load")
    # the buggy path pays one extra cold load on the critical path
    assert buggy_wait - fixed_wait > 0.5 * delay, (buggy_wait, fixed_wait)


def test_fused_attention_spools_fewer_bytes():
    """A spooled layer's residuals are the leaves of its vjp closure:
    through the fused causal pair (interpreted here) attention leaves
    (q, k, v, out, lse) there, through the XLA path the stacked f32
    tiles of its scan. One bf16 GQA layer at 1 x 256 tokens."""
    from repro.configs.paper_models import gpt
    cfg = dataclasses.replace(gpt(256, 1, vocab=512), num_kv_heads=1)
    spooled = {}
    for impl in ("xla", "pallas_interpret"):
        settings = RunSettings(attn_impl=impl, attn_chunk=128,
                               activation_policy="spool")
        with TrainSession(cfg, engine="jit", optimizer="adamw",
                          batch_size=1, seq_len=256, seed=3,
                          ckpt_every=0, settings=settings,
                          min_offload_elements=MIN_OFF,
                          io=SpoolIoConfig(
                              backend="mem",
                              host_offload="activations")) as sess:
            result = sess.run(1)
            assert np.isfinite(result.losses).all()
            spooled[impl] = sess.spool.stats.bytes_offloaded
    assert 0 < spooled["pallas_interpret"] < spooled["xla"]
