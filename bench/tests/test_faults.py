"""A whole run with the timed path broken underneath reads
`correct: false`. The look for a chip is skipped (the harness's
`run_cell` is called directly). The program's jitted step is wrapped so
that it returns its state unchanged or leaves half of each batch's
tokens out of the loss; in the spool cell, the spool hooks serve
layer 1's backward the residuals of layer 0."""
import time

import numpy as np
import pytest

import jax

from bench import harness


def unchanged(step):
    def f(params, opt_state, batch):
        p = jax.tree.map(lambda a: a.copy(), params)
        o = jax.tree.map(lambda a: a.copy(), opt_state)
        _, _, metrics = step(p, o, batch)
        return params, opt_state, metrics
    return f


def half_batch(step):
    def f(params, opt_state, batch):
        labels = np.array(batch["labels"])
        labels[:, labels.shape[1] // 2:] = -1
        return step(params, opt_state, dict(batch, labels=labels))
    return f


def broken(fault, make):
    def build(*a, **k):
        step = make(*a, **k)
        f = fault(step)
        f.lower = step.lower        # the memory reading lowers the step
        return f
    return build


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_a_broken_step_is_not_correct(tiny_bench, monkeypatch, fault):
    from repro.session import session as session_mod
    monkeypatch.setattr(session_mod, "make_host_train_step",
                        broken(fault, session_mod.make_host_train_step))
    res = harness.run_cell("tiny.remat", 11, 0.5, False,
                           time.perf_counter())
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_a_broken_step_of_a_two_segment_family_is_not_correct(
        tiny_bench, monkeypatch, fault):
    """The same faults in the test family with a dense segment and an
    expert segment (bench/tests/data/families/moe.py)."""
    from repro.session import session as session_mod
    monkeypatch.setattr(session_mod, "make_host_train_step",
                        broken(fault, session_mod.make_host_train_step))
    res = harness.run_cell("tiny_moe.remat", 13, 0.5, False,
                           time.perf_counter())
    assert res["correct"] is False, res["compared"]


def test_residuals_from_the_wrong_layer_are_not_correct(tiny_bench,
                                                        monkeypatch):
    from repro.core.hooks import HookBridge
    offload, fetch = HookBridge.offload, HookBridge.fetch
    kept = {}

    def keep_layer0(self, step, stage, arrays, **kw):
        if stage == 0:
            kept[step] = [np.array(a, copy=True) for a in arrays]
        return offload(self, step, stage, arrays, **kw)

    def serve_layer0(self, step, stage, **kw):
        out = fetch(self, step, stage, **kw)
        return [a.copy() for a in kept[step]] if stage == 1 else out

    monkeypatch.setattr(HookBridge, "offload", keep_layer0)
    monkeypatch.setattr(HookBridge, "fetch", serve_layer0)
    res = harness.run_cell("tiny.spool", 12, 0.5, False,
                           time.perf_counter())
    assert kept, "the spool hooks never ran"
    assert res["correct"] is False, res["compared"]
