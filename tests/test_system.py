"""System-level integration tests: the paper's claims, end to end, on the
runnable (staged + spool) TBA path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import small_bert, small_gpt
from repro.core.staged import StagedTrainer
from repro.models.api import build_model
from repro.models.transformer import RunSettings
from repro.optim.optimizers import sgd

B, S = 4, 64
MIN_OFF = 2 ** 10


def _setup(cfg, strategy, seed=0):
    cfg = dataclasses.replace(cfg, dtype="float32")
    api = build_model(cfg)
    settings = RunSettings(attn_impl="xla", attn_chunk=64,
                           param_dtype="float32")
    opt = sgd(1e-2)
    tr = StagedTrainer(api, settings, opt, strategy=strategy,
                       min_offload_elements=MIN_OFF)
    params = api.init(jax.random.key(seed))
    return api, tr, params, opt.init(params)


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


@pytest.fixture(scope="module")
def runs():
    """One fixture runs all three strategies on the same model/batch.

    Six layers (not three) so the forward pass is several times longer
    than one store's latency — the paper's operating regime, where
    writes land during forward and the offload peak reduction is
    unambiguous rather than a race with the first backward fetch."""
    cfg = small_gpt(128, 6)
    out = {}
    for strategy in ("keep", "offload", "recompute"):
        api, tr, params, opt_state = _setup(cfg, strategy)
        batch = _batch(cfg)
        reports, losses = [], []
        for step in range(4):
            params, opt_state, rep = tr.train_step(params, opt_state,
                                                   [batch])
            reports.append(rep)
            losses.append(rep.loss)
        out[strategy] = {"reports": reports, "losses": losses,
                         "params": params, "plan": tr.plan,
                         "profiles": tr._profiles}
        tr.close()
    return out


def _planned_bytes(run):
    """Residual bytes the adaptive plan chose to offload, from THIS
    process's own profiling step — the footprint assertions measure the
    reduction against this instead of a fixed fraction, so they hold on
    any machine regardless of how much the measured bandwidth lets the
    planner offload."""
    plan, profiles = run["plan"], run["profiles"]
    if plan is None or profiles is None:
        return 0
    return sum(p.bytes for p, off in zip(profiles, plan.offload) if off)


def test_strategies_numerically_identical(runs):
    """Offload and recompute must not change the math (paper: offloading
    is transparent)."""
    for a, b in [("keep", "offload"), ("keep", "recompute")]:
        np.testing.assert_allclose(runs[a]["losses"], runs[b]["losses"],
                                   rtol=1e-5, atol=1e-6)
        la = jax.tree.leaves(runs[a]["params"])
        lb = jax.tree.leaves(runs[b]["params"])
        for x, y in zip(la, lb):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-4, atol=2e-5)


def test_offload_reduces_activation_peak(runs):
    """Paper Fig. 7/10: the activation peak drops with offloading.
    Steps 0-1 are excluded: 0 profiles (and compiles), 1 pays the
    plan transition — the claim is about steady state."""
    keep = max(r.peak_activation_bytes for r in runs["keep"]["reports"])
    off = max(r.peak_activation_bytes
              for r in runs["offload"]["reports"][2:])
    planned = _planned_bytes(runs["offload"])
    if planned == 0:
        pytest.skip("measured bandwidth planned no offloads here")
    # stores overlap forward, so some offloaded residuals are still
    # in flight at the peak: claim half the planned bytes
    assert off <= keep - 0.5 * planned, (off, keep, planned)


def test_offload_reduces_backward_begin_footprint(runs):
    """Paper Fig. 7: the begin-of-backward footprint drops ~45%."""
    keep = max(r.backward_begin_bytes for r in runs["keep"]["reports"])
    off = max(r.backward_begin_bytes
              for r in runs["offload"]["reports"][2:])
    planned = _planned_bytes(runs["offload"])
    if planned == 0:
        pytest.skip("measured bandwidth planned no offloads here")
    # by backward begin every store has landed; the last offloaded
    # module is already reloaded, so claim half the planned bytes
    assert off <= keep - 0.5 * planned, (off, keep, planned)


def test_recompute_has_lower_peak_but_same_loss(runs):
    keep = max(r.peak_activation_bytes for r in runs["keep"]["reports"])
    rec = max(r.peak_activation_bytes
              for r in runs["recompute"]["reports"])
    assert rec < keep


def test_offload_actually_spools_to_disk(runs):
    stats = runs["offload"]["reports"][-1].stats
    assert stats.bytes_offloaded > 0
    assert stats.num_stores > 0


def test_adaptive_plan_exists_after_profile_step(runs):
    rep = runs["offload"]["reports"][-1]
    assert rep.plan is not None
    # the last module (loss head) is never offloaded (§3.2 circled-4)
    assert not rep.plan.offload[-1]


class _FullDevice:
    """A device whose memory is reported as all in use."""

    def memory_stats(self):
        return {"bytes_in_use": 1 << 34, "bytes_limit": 1 << 34}


def test_staged_forward_waits_for_device_room():
    """When the device reports too little room for the next stage's
    residuals, the forward drains the store queue before running it;
    the CPU reports no limit and never waits. Waiting changes no math."""
    cfg = small_gpt(128, 3)
    batch = _batch(cfg)
    waits, losses = {}, {}
    for full in (False, True):
        api, tr, params, opt_state = _setup(cfg, "offload")
        if full:
            tr._device = _FullDevice()
        _, _, rep = tr.train_step(params, opt_state, [batch])
        waits[full], losses[full] = tr.device_room_waits, rep.loss
        tr.close()
    assert waits[False] == 0
    # every stage after the first has a profiled size to make room for
    assert waits[True] == len(tr._stages) - 1
    assert losses[True] == losses[False]


def test_staged_matches_jit_training():
    """The staged trainer is numerically the same training algorithm as a
    whole-step jit (the system's central correctness invariant)."""
    cfg = dataclasses.replace(small_bert(128, 2), dtype="float32")
    api = build_model(cfg)
    settings = RunSettings(attn_impl="xla", attn_chunk=64,
                           param_dtype="float32")
    opt = sgd(1e-2)
    batch = _batch(cfg)

    params = api.init(jax.random.key(7))
    tr = StagedTrainer(api, settings, opt, strategy="offload",
                       min_offload_elements=MIN_OFF)
    # both steps donate their inputs: each run starts from its own copy
    p_staged = jax.tree.map(jnp.copy, params)
    os_staged = opt.init(p_staged)
    for _ in range(2):
        p_staged, os_staged, rep = tr.train_step(p_staged, os_staged,
                                                 [batch])
    tr.close()

    @jax.jit
    def step(p, o, b):
        (_, m), g = jax.value_and_grad(api.loss, has_aux=True)(
            p, b, settings)
        return opt.update(g, o, p)

    p_jit, o_jit = params, opt.init(params)
    for _ in range(2):
        p_jit, o_jit = step(p_jit, o_jit, batch)

    for a, b in zip(jax.tree.leaves(p_staged), jax.tree.leaves(p_jit)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
