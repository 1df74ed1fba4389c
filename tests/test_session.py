"""Tests for the unified TrainSession front door: engine parity, policy
resolution, SpoolIoConfig honored by the jit engine, unified metrics,
and resource cleanup (spool temp dirs, worker threads)."""
import dataclasses
import glob
import json
import os
import tempfile

import numpy as np
import pytest

from repro.configs.base import SpoolIoConfig
from repro.configs.paper_models import small_gpt
from repro.core.policies import (AdaptivePolicy, KeepPolicy,
                                 RecomputePolicy, SpoolPolicy,
                                 resolve_policy)
from repro.core.staged import StagedTrainer
from repro.session import TrainSession

MIN_OFF = 2 ** 8


def _cfg(hidden=128, layers=2):
    return dataclasses.replace(small_gpt(hidden, layers),
                               dtype="float32")


def _session(engine, **kw):
    kw.setdefault("optimizer", "adamw")
    kw.setdefault("lr", 1e-3)
    kw.setdefault("batch_size", 2)
    kw.setdefault("seq_len", 32)
    kw.setdefault("seed", 3)
    kw.setdefault("ckpt_every", 0)
    kw.setdefault("min_offload_elements", MIN_OFF)
    return TrainSession(_cfg(), engine=engine, **kw)


# --------------------------------------------------------- engine parity

@pytest.fixture(scope="module")
def parity():
    """Both engines, identical config, 3 steps on small-gpt."""
    out = {}
    for engine, io in [
        ("staged", None),
        ("jit", SpoolIoConfig(backend="mem", host_offload="opt_state")),
    ]:
        with _session(engine, io=io) as sess:
            result = sess.run(3)
            out[engine] = {
                "result": result,
                "losses": result.losses,
                "spool_backend": (type(sess.spool.backend).__name__
                                  if sess.spool else None),
                "spool_stats": (dataclasses.replace(sess.spool.stats)
                                if sess.spool else None),
                "io_writes": (sess.spool.backend.stats.num_writes
                              if sess.spool else 0),
            }
    return out


def test_both_engines_finite_matching_losses(parity):
    """Same arch/seed/optimizer through one front door: both engines
    produce finite losses of matching magnitude (the staged chain is the
    same training algorithm as the whole-step jit)."""
    ls, lj = parity["staged"]["losses"], parity["jit"]["losses"]
    assert len(ls) == len(lj) == 3
    assert np.all(np.isfinite(ls)) and np.all(np.isfinite(lj))
    np.testing.assert_allclose(ls, lj, rtol=5e-3)


def test_both_engines_report_matching_grad_norm(parity):
    """Both engines report each step's gradient norm; the first step
    starts from the same params and batch, so the norms agree as closely
    as the losses do."""
    gs, gj = ([r.extra["grad_norm"] for r in parity[e]["result"].reports]
              for e in ("staged", "jit"))
    assert len(gs) == len(gj) == 3
    assert np.all(np.isfinite(gs)) and np.all(np.array(gs) > 0)
    np.testing.assert_allclose(gs[0], gj[0], rtol=5e-3)


def test_reports_unified_schema(parity):
    for engine in ("staged", "jit"):
        reports = parity[engine]["result"].reports
        assert [r.step for r in reports] == [1, 2, 3]
        assert all(r.engine == engine for r in reports)
        assert all(r.step_time > 0 for r in reports)
        assert all(r.tokens_per_s > 0 for r in reports)
        rec = reports[-1].to_metrics()
        assert rec["engine"] == engine and rec["step"] == 3
        assert "loss" in rec and "step_time_s" in rec


def test_jit_engine_honors_spool_backend(parity):
    """The jit engine builds its host-offload spool on the
    SpoolIoConfig-selected backend, and real bytes move through it."""
    assert parity["jit"]["spool_backend"] == "HostMemoryBackend"
    stats = parity["jit"]["spool_stats"]
    assert stats.num_stores > 0
    # every store either landed on the backend or was forwarded in
    # memory before the write started — both are real spool traffic
    assert parity["jit"]["io_writes"] > 0 or stats.bytes_forwarded > 0


def test_host_offload_is_transparent():
    """Staging the optimizer state through the spool between steps must
    not change the math."""
    with _session("jit") as plain:
        base = plain.run(3).losses
    with _session("jit", io=SpoolIoConfig(
            backend="mem", host_offload="opt_state")) as offl:
        offloaded = offl.run(3).losses
    np.testing.assert_allclose(base, offloaded, rtol=1e-6)


def test_host_offload_survives_per_step_checkpointing():
    """Regression: checkpointing while the opt-state store is still
    queued must not cancel the write (the checkpoint peek is
    non-consuming), or the next step's fetch dies."""
    d = tempfile.mkdtemp()
    with _session("jit", ckpt_dir=d, ckpt_every=1,
                  io=SpoolIoConfig(backend="fs", directory=d + "/spool",
                                   store_threads=1,
                                   host_offload="opt_state")) as sess:
        losses = sess.run(3).losses
    assert np.all(np.isfinite(losses))


def test_run_twice_reports_are_per_run():
    with _session("jit") as sess:
        r1 = sess.run(2)
        r2 = sess.run(2)
    assert [r.step for r in r1.reports] == [1, 2]
    assert [r.step for r in r2.reports] == [3, 4]
    assert len(sess.reports) == 4     # session keeps the full stream


def test_jit_metrics_keep_engine_aux_fields():
    """The unified schema must not drop the jit engine's aux metrics
    (ce/tokens; moe_lb/moe_z on MoE archs) that the seed JSONL had."""
    d = tempfile.mkdtemp()
    path = os.path.join(d, "metrics.jsonl")
    with _session("jit", metrics_path=path) as sess:
        sess.run(2)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    for rec in lines:
        assert "ce" in rec and "tokens" in rec
        assert rec["engine"] == "jit"


# ------------------------------------------------------------- policies

def test_policy_resolution_matrix():
    assert isinstance(resolve_policy(None), AdaptivePolicy)
    assert isinstance(resolve_policy("keep"), KeepPolicy)
    assert isinstance(resolve_policy("recompute"), RecomputePolicy)
    assert isinstance(resolve_policy("adaptive"), AdaptivePolicy)
    assert isinstance(resolve_policy(strategy="offload"), AdaptivePolicy)
    assert isinstance(resolve_policy(strategy="offload", adaptive=False),
                      SpoolPolicy)
    pol = KeepPolicy()
    assert resolve_policy(pol) is pol
    with pytest.raises(ValueError):
        resolve_policy(pol, strategy="keep")   # both call shapes at once
    with pytest.raises(ValueError):
        resolve_policy("warp-drive")


def test_legacy_strategy_kwargs_map_to_policies():
    """Seed call shapes keep working: strategy= + adaptive= on the
    trainer construct the equivalent policy objects."""
    from repro.models.api import build_model
    from repro.models.transformer import RunSettings
    from repro.optim.optimizers import sgd

    api = build_model(_cfg(128, 1))
    settings = RunSettings(attn_impl="xla", attn_chunk=32,
                           param_dtype="float32")
    tr = StagedTrainer(api, settings, sgd(1e-2), strategy="keep")
    assert isinstance(tr.policy, KeepPolicy)
    assert tr.strategy == "keep" and not tr.adaptive
    tr.close()
    tr = StagedTrainer(api, settings, sgd(1e-2), strategy="offload",
                       adaptive=False)
    assert isinstance(tr.policy, SpoolPolicy)
    tr.close()
    tr = StagedTrainer(api, settings, sgd(1e-2))
    assert isinstance(tr.policy, AdaptivePolicy) and tr.adaptive
    tr.close()


def test_jit_engine_rejects_policy():
    with pytest.raises(ValueError):
        TrainSession(_cfg(), engine="jit", policy="keep")


# ------------------------------------------------------------- cleanup

def test_trainer_cleans_up_owned_tmpdir():
    """The seed leaked one tba_spool_* temp dir per trainer."""
    from repro.models.api import build_model
    from repro.models.transformer import RunSettings
    from repro.optim.optimizers import sgd

    pattern = os.path.join(tempfile.gettempdir(), "tba_spool_*")
    before = set(glob.glob(pattern))
    api = build_model(_cfg(128, 1))
    settings = RunSettings(attn_impl="xla", attn_chunk=32,
                           param_dtype="float32")
    tr = StagedTrainer(api, settings, sgd(1e-2))
    assert set(glob.glob(pattern)) - before      # dir exists while open
    tr.close()
    tr.close()                                   # idempotent
    assert not (set(glob.glob(pattern)) - before)

    # a user-named spool_dir is NOT removed
    keep_dir = tempfile.mkdtemp(prefix="user_spool_")
    tr = StagedTrainer(api, settings, sgd(1e-2), spool_dir=keep_dir)
    tr.close()
    assert os.path.isdir(keep_dir)


def test_session_metrics_jsonl_unified():
    d = tempfile.mkdtemp()
    path = os.path.join(d, "metrics.jsonl")
    with _session("staged", metrics_path=path) as sess:
        sess.run(2)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    for rec in lines:
        for key in ("step", "engine", "loss", "step_time_s",
                    "tokens_per_s", "peak_activation_bytes",
                    "bytes_offloaded"):
            assert key in rec, key
    assert lines[0]["engine"] == "staged"


# ----------------------- data-plane parity matrix (backend x codec)


@pytest.fixture(scope="module")
def no_offload_losses():
    """Baseline: staged engine, keep-everything policy — the spool
    never touches a byte of residuals."""
    with _session("staged", policy=KeepPolicy()) as sess:
        return sess.run(2).losses


@pytest.mark.parametrize("backend", ["fs", "striped", "mem", "tiered",
                                     "managed", "aio"])
@pytest.mark.parametrize("codec", ["raw", "byteplane"])
def test_losses_bitwise_identical_across_data_planes(
        backend, codec, no_offload_losses, tmp_path):
    """The whole zero-copy data plane (vectored writes, pooled aligned
    loads, O_DIRECT, byte-plane codec) must be invisible to training:
    losses stay BITWISE identical to the no-offload baseline on every
    backend x codec pair."""
    io = SpoolIoConfig(
        backend=backend, codec=codec,
        directory=str(tmp_path / "spool"),
        stripe_dirs=(tuple(str(tmp_path / f"s{i}") for i in range(2))
                     if backend == "striped" else ()),
        # a tight tiered budget forces real spills to the lower tier
        host_mem_budget_bytes=64 << 10,
        pool_bytes=8 << 20)
    with _session("staged", policy=SpoolPolicy(), io=io) as sess:
        losses = sess.run(2).losses
        io_stats = sess.spool.backend.stats
        forwarded = sess.spool.stats.bytes_forwarded
    assert losses == no_offload_losses, \
        f"{backend}/{codec} changed training: {losses}"
    # real bytes moved through the data plane (or were forwarded from
    # in-flight stores — still real spool traffic)
    assert io_stats.num_writes > 0 or forwarded > 0
