"""Fault-tolerance tests on the TrainLoop: checkpoint/restart determinism,
preemption handling, straggler detection."""
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import ShardedLoader, SyntheticMarkovLM
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.trainer import StragglerWatchdog, TrainLoop, TrainState

REPO = Path(__file__).resolve().parents[1]


def _quadratic_setup(ckpt_dir, metrics=None, slow_steps=()):
    """A tiny 'model' whose params integrate the data stream — any
    divergence between runs shows up immediately."""
    calls = {"n": 0}

    def step_fn(params, opt_state, batch):
        calls["n"] += 1
        if calls["n"] in slow_steps:
            time.sleep(0.25)
        g = jnp.asarray(batch["tokens"], jnp.float32).mean()
        params = {"w": params["w"] - 0.01 * (params["w"] - g)}
        return params, opt_state, {"loss": float(params["w"].sum())}

    src = SyntheticMarkovLM(128, seed=9)
    loader = ShardedLoader(src, global_batch=4, seq_len=8, prefetch=0)
    loop = TrainLoop(
        step_fn=step_fn,
        init_state=TrainState(0, {"w": jnp.zeros((2,))}, {}),
        loader=loader, ckpt_dir=ckpt_dir, ckpt_every=5,
        metrics_path=metrics,
        watchdog=StragglerWatchdog(window=16, threshold=2.0))
    return loop


def test_checkpoint_restart_bitwise_identical():
    d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    # uninterrupted 20 steps
    loop_a = _quadratic_setup(d1)
    final_a = loop_a.run(20)
    # interrupted at 10, resumed into a NEW loop (fresh process semantics)
    loop_b1 = _quadratic_setup(d2)
    loop_b1.run(10)
    loop_b2 = _quadratic_setup(d2)
    assert loop_b2.resume()
    assert loop_b2.state.step == 10
    final_b = loop_b2.run(10)
    assert final_a.step == final_b.step == 20
    np.testing.assert_array_equal(np.asarray(final_a.params["w"]),
                                  np.asarray(final_b.params["w"]))


def test_preemption_saves_final_checkpoint():
    d = tempfile.mkdtemp()
    loop = _quadratic_setup(d)
    loop.request_preemption()        # simulated SIGTERM before any step
    final = loop.run(50)
    assert final.step == 0
    assert loop.ckpt.latest_step() == 0   # final checkpoint committed


def test_straggler_watchdog_flags_slow_steps():
    d = tempfile.mkdtemp()
    loop = _quadratic_setup(d, slow_steps={15, 16})
    loop.run(20)
    flagged = {f["step"] for f in loop.watchdog.flagged}
    assert {15, 16} & flagged


def test_metrics_jsonl_written():
    import json
    d = tempfile.mkdtemp()
    path = os.path.join(d, "metrics.jsonl")
    loop = _quadratic_setup(d, metrics=path)
    loop.run(5)
    loop.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 5
    assert all("step_time_s" in l and "loss" in l for l in lines)


def _finite_loop(batches, ckpt_dir, metrics=None):
    def step_fn(params, opt_state, batch):
        g = jnp.asarray(batch["tokens"], jnp.float32).mean()
        params = {"w": params["w"] - 0.01 * (params["w"] - g)}
        return params, opt_state, {"loss": float(params["w"].sum())}

    return TrainLoop(
        step_fn=step_fn,
        init_state=TrainState(0, {"w": jnp.zeros((2,))}, {}),
        loader=batches, ckpt_dir=ckpt_dir, ckpt_every=0,
        metrics_path=metrics)


def test_loader_exhaustion_ends_cleanly_with_final_checkpoint():
    """Regression: `next(it)` let StopIteration escape run(), skipping
    the final checkpoint (and the staged-opt-state rematerialization).
    A dry loader must end the loop cleanly instead."""
    d = tempfile.mkdtemp()
    batches = [{"tokens": np.full((2, 4), i)} for i in range(3)]
    loop = _finite_loop(batches, d)
    final = loop.run(10)            # asks for more steps than data
    loop.close()
    assert final.step == 3          # every batch consumed, then stop
    assert loop.ckpt.latest_step() == 3   # final checkpoint committed


def test_tokens_per_s_masks_padding():
    """Regression: tokens/s counted padded positions. With labels
    present, only labels >= 0 are real targets."""
    import json
    d = tempfile.mkdtemp()
    path = os.path.join(d, "metrics.jsonl")
    labels = np.full((2, 8), -1)
    labels[:, :3] = 5               # 6 real targets out of 16 positions
    batches = [{"tokens": np.zeros((2, 8), np.int32), "labels": labels}]
    loop = _finite_loop(batches, d, metrics=path)
    loop.run(1)
    loop.close()
    rec = json.loads(open(path).readline())
    tokens = rec["tokens_per_s"] * rec["step_time_s"]
    assert abs(tokens - 6) < 1e-6 * 6, rec


def test_compile_cache_default_dir(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache is the checkout's
    fixed `.jax_cache` (no pid, time or temp name in the path)."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper sets no other directory."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    code = ("import jax\n"
            "from repro.runtime.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(tmp_path.iterdir())
