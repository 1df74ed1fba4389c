"""Fault-tolerant training driver.

Responsibilities (the ones a 1000-node fleet actually needs):
  * checkpoint/restart — periodic async checkpoints (model + optimizer +
    data cursor), `--resume` picks up the latest committed step;
  * preemption handling — SIGTERM/SIGINT trap requests a final checkpoint
    at the next step boundary, then exits cleanly (the cluster scheduler's
    contract);
  * straggler mitigation — per-step wall-time watchdog keeps a rolling
    median; steps slower than `threshold x median` are recorded and
    surfaced through a callback (on a real fleet this feeds the
    repair/reschedule controller; here the hook is unit-tested directly);
  * elastic restart — restore() takes the *current* mesh's shardings, so
    a checkpoint taken on one topology restores onto another;
  * metrics — JSONL lines per step (loss, step time, tokens/s);
  * host offload — with an `ActivationSpool` attached (built from a
    `SpoolIoConfig` by `TrainSession`), two modes share the spool's
    backend/codec selection with the staged engine:
      - "opt_state": the optimizer state is staged through the storage
        backend between steps — offloaded asynchronously after the
        update, fetched (with tensor forwarding) just before the next
        one (10Cache-style optimizer-state tiering);
      - "activations": per-layer residuals stream through the backend
        *inside* the jitted step via the repro.core.hooks io_callback
        path — the step_fn owns that traffic (the loop only holds the
        spool for stats/teardown), so the two modes coexist as
        alternatives on one spool.
"""
from __future__ import annotations

import json
import os
import signal
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import numpy as np

import jax

from repro import obs
from repro.ckpt.checkpoint import (CheckpointManager, restore_train_state,
                                   save_train_state)


@dataclass
class TrainState:
    step: int
    params: Any
    opt_state: Any


def batch_tokens(batch) -> int:
    """Tokens a batch contributes to throughput. With labels present
    only real targets count (labels >= 0) — shape products overcount
    padded positions. Returns 0 when the batch carries no tokens."""
    if isinstance(batch, dict) and "labels" in batch:
        return int(np.sum(np.asarray(batch["labels"]) >= 0))
    if isinstance(batch, dict) and "tokens" in batch:
        return int(np.prod(batch["tokens"].shape))
    return 0


class StragglerWatchdog:
    """Rolling-median step-time monitor."""

    def __init__(self, *, window: int = 32, threshold: float = 2.0,
                 on_straggler: Optional[Callable[[int, float, float],
                                                 None]] = None):
        self.window = window
        self.threshold = threshold
        self.on_straggler = on_straggler
        self.times: List[float] = []
        self.flagged: List[Dict] = []

    def record(self, step: int, dt: float) -> bool:
        history = self.times[-self.window:]
        is_straggler = False
        if len(history) >= 8:
            med = statistics.median(history)
            if dt > self.threshold * med:
                is_straggler = True
                self.flagged.append({"step": step, "dt": dt, "median": med})
                if self.on_straggler:
                    self.on_straggler(step, dt, med)
        self.times.append(dt)
        return is_straggler


class TrainLoop:
    def __init__(self, *, step_fn: Callable, init_state: TrainState,
                 loader, ckpt_dir: str, ckpt_every: int = 100,
                 keep_last: int = 3, metrics_path: Optional[str] = None,
                 watchdog: Optional[StragglerWatchdog] = None,
                 shardings: Any = None,
                 spool: Any = None,
                 host_offload: Any = False,
                 opt_bridge: Any = None,
                 on_step: Optional[Callable[[int, float, Any, Any],
                                            None]] = None,
                 install_signal_handlers: bool = False):
        self.step_fn = step_fn
        self.state = init_state
        self.loader = loader
        self.ckpt = CheckpointManager(ckpt_dir, keep_last=keep_last)
        self.ckpt_every = ckpt_every
        self.metrics_path = metrics_path
        self.watchdog = watchdog or StragglerWatchdog()
        self.shardings = shardings
        # host offload: the spool is owned by the caller (TrainSession).
        # Mode "opt_state" leases per-step records here; "activations"
        # is driven from inside step_fn (repro.core.hooks) and the loop
        # only carries the spool. Legacy bool maps onto "opt_state".
        if isinstance(host_offload, bool):
            host_offload = "opt_state" if host_offload else "none"
        assert host_offload in ("none", "opt_state", "activations"), \
            host_offload
        # Eager overlap (repro.optim.overlap.OptBridge): the bridge owns
        # per-layer opt-state placement, so the serial whole-state
        # staging path is retired for this loop — the step_fn's grad
        # taps drive all opt I/O and the loop's opt_state is a light
        # (step, None, None) husk the bridge can rematerialize.
        self.opt_bridge = opt_bridge
        if opt_bridge is not None and host_offload == "opt_state":
            host_offload = "none"
        self.spool = spool
        self.host_offload = (host_offload if spool is not None
                             else "none")
        self.on_step = on_step
        # host seconds the last step's dispatch took: the step_fn call
        # (opt-state fetch included) until it returned, before the wait
        self.dispatch_time = 0.0
        self._opt_tx = None          # live SpoolStepTransaction, if any
        self._preempted = False
        self._metrics_f = open(metrics_path, "a") if metrics_path else None
        if install_signal_handlers:
            for sig in (signal.SIGTERM, signal.SIGINT):
                signal.signal(sig, self._on_preempt)

    # ------------------------------------------------------------ hooks

    def _on_preempt(self, signum, frame):
        # async-signal-safe: just set a flag; the loop checkpoints at the
        # next step boundary (the paper's framework-interop requirement
        # maps here to not corrupting in-flight async spools).
        self._preempted = True

    def request_preemption(self):
        """Test hook: simulate the scheduler's SIGTERM."""
        self._preempted = True

    # ----------------------------------------------- host offload (jit)

    def _acquire_opt_state(self):
        """The optimizer state, fetched back from the spool if the
        previous step staged it out (forwarding applies: a store still
        in flight is upgraded in memory, not re-read)."""
        if self._opt_tx is None:
            return self.state.opt_state
        tx, self._opt_tx = self._opt_tx, None
        with obs.span("engine.opt_fetch", cat="engine",
                      step=self.state.step):
            opt_state = tx.fetch(0)
        tx.close()                  # drops the record + deletes the blob
        return opt_state

    def _stage_opt_state(self, opt_state, step: int):
        """Async-offload the fresh optimizer state through the spool;
        returns what TrainState should hold (None while spooled — the
        spool owns the only strong reference until the next acquire)."""
        if self.host_offload != "opt_state":
            return opt_state
        with obs.span("engine.opt_stage", cat="engine", step=step):
            tx = self.spool.step(f"opt{step}")
            tx.offload(0, opt_state)
        self._opt_tx = tx
        return None

    # ------------------------------------------------------- checkpoints

    def _save(self, final: bool = False):
        opt_state = self.state.opt_state
        if self.opt_bridge is not None and self.opt_bridge.seeded:
            # per-layer moments live on the spool (plus the bridge's
            # in-memory rest-of-tree moments) — reassemble the full
            # OptState non-consumingly for the checkpoint
            opt_state = self.opt_bridge.materialize()
        elif opt_state is None and self._opt_tx is not None:
            # staged out between steps: materialize non-consumingly —
            # peek() must not cancel the queued store, or the next
            # step's fetch would find neither arrays nor blob
            opt_state = self._opt_tx.peek(0)
        save_train_state(self.ckpt, self.state.step, self.state.params,
                         opt_state, self.loader, final=final)

    def resume(self) -> bool:
        """Restore the latest checkpoint if present. Returns True if
        restored. Reshards onto the current mesh via self.shardings."""
        restored = restore_train_state(
            self.ckpt, self.state.params, self.state.opt_state,
            self.loader, shardings=self.shardings)
        if restored is None:
            return False
        self.state = TrainState(*restored)
        return True

    # ------------------------------------------------------------- loop

    def run(self, num_steps: int) -> TrainState:
        it = iter(self.loader)
        target = self.state.step + num_steps
        while self.state.step < target and not self._preempted:
            step = self.state.step
            try:
                with obs.span("loader.next", cat="engine", step=step):
                    batch = next(it)
            except StopIteration:
                # a finite loader ran dry: end the loop cleanly — the
                # final checkpoint and the staged-opt-state
                # rematerialization below must still run
                break
            # the step is two spans, not one around both: the profiler
            # trace names a device idle gap by the host span covering
            # it, and a span around both phases would take the name of
            # every gap that straddles them
            t0 = time.perf_counter()
            with obs.span("engine.dispatch", cat="engine", step=step):
                params, opt_state, metrics = self.step_fn(
                    self.state.params, self._acquire_opt_state(), batch)
            t_dispatched = time.perf_counter()
            with obs.span("engine.wait", cat="engine", step=step):
                jax.block_until_ready(jax.tree.leaves(params)[0])
            dt = time.perf_counter() - t0
            self.dispatch_time = t_dispatched - t0
            opt_state = self._stage_opt_state(opt_state,
                                              self.state.step + 1)
            self.state = TrainState(self.state.step + 1, params, opt_state)
            self.watchdog.record(self.state.step, dt)
            self._log(metrics, dt, batch)
            if self.on_step:
                with obs.span("session.report", cat="engine", step=step):
                    self.on_step(self.state.step, dt, metrics, batch)
            if self.ckpt_every and \
                    self.state.step % self.ckpt_every == 0:
                self._save()
        # rematerialize a staged-out optimizer state before the final
        # checkpoint / before handing the state back
        if self._opt_tx is not None:
            self.state = TrainState(self.state.step, self.state.params,
                                    self._acquire_opt_state())
        if self.opt_bridge is not None and self.opt_bridge.seeded:
            self.state = TrainState(self.state.step, self.state.params,
                                    self.opt_bridge.materialize())
        self._save(final=True)
        return self.state

    def _log(self, metrics, dt, batch):
        if self._metrics_f is None:
            return
        rec = {"step": self.state.step, "step_time_s": dt}
        tokens = batch_tokens(batch)
        if tokens:
            rec["tokens_per_s"] = tokens / dt
        for k, v in (metrics or {}).items():
            try:
                rec[k] = float(v)
            except (TypeError, ValueError):
                pass
        self._metrics_f.write(json.dumps(rec) + "\n")
        self._metrics_f.flush()

    def close(self):
        if self._opt_tx is not None:
            self._opt_tx.close()
            self._opt_tx = None
        if self._metrics_f:
            self._metrics_f.close()
        self.ckpt.wait()
