"""Unified per-step report emitted by both training engines.

One schema for the staged (TBA) engine and the whole-step jit engine, so
`TrainSession` callers, the metrics JSONL, and the benchmarks read the
same fields regardless of which engine produced a step. The staged
engine fills every field; the jit engine leaves the activation-footprint
fields at 0 (XLA owns device memory there) and fills the spool fields
only when the host-offload path is active.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional


@dataclass
class StepReport:
    loss: float
    step_time: float
    peak_activation_bytes: int = 0
    backward_begin_bytes: int = 0
    stats: Any = None                  # SpoolStats (or None: no spool)
    plan: Any = None                   # OffloadPlan (staged+adaptive only)
    step: int = -1                     # optimizer step index (-1: unset)
    engine: str = ""                   # "staged" | "jit"
    tokens_per_s: float = 0.0
    # jit engine: host seconds from the step call until it returned,
    # the rest of step_time being the wait for the device (0: staged)
    dispatch_time: float = 0.0
    # XLA backend compiles during the step (repro.obs.compiles)
    compiles: int = 0
    # engine-specific scalar metrics (jit: the step's full aux dict —
    # ce, tokens, moe_lb/moe_z on MoE archs, ...); merged into the JSONL
    extra: Dict[str, float] = field(default_factory=dict)
    # repro.obs overlap analysis for THIS step's trace window (see
    # repro.obs.overlap.analyze); emitted with an obs_ prefix
    obs: Optional[Dict[str, Any]] = None
    # per-shard HookBridge traffic deltas for this step, keyed by shard
    # id ("global" on a single device)
    shard_stats: Optional[Dict[str, Dict[str, float]]] = None
    # cache-manager block for this step (managed backend only): counter
    # deltas + residency gauges from CacheManager.metrics_delta; emitted
    # with a cache_ prefix
    cache: Optional[Dict[str, Any]] = None
    # resilience block for this step (any spool): retry / fallback /
    # re-plan / rebalance counter deltas plus backend-health gauges
    # (repro.resilience); emitted with a resilience_ prefix
    resilience: Optional[Dict[str, Any]] = None

    def to_metrics(self) -> Dict[str, Any]:
        """Flat JSON-able dict — the unified metrics-JSONL schema.

        The spool fields are PER-STEP deltas: both engines snapshot
        `SpoolStats` at step boundaries and hand the report the
        difference, so a JSONL row describes its own step, not the run
        so far."""
        rec: Dict[str, Any] = {
            "step": self.step,
            "engine": self.engine,
            "loss": float(self.loss),
            "step_time_s": float(self.step_time),
            "tokens_per_s": float(self.tokens_per_s),
            "dispatch_time_s": float(self.dispatch_time),
            "compiles": int(self.compiles),
            "peak_activation_bytes": int(self.peak_activation_bytes),
            "backward_begin_bytes": int(self.backward_begin_bytes),
        }
        if self.stats is not None:
            rec["bytes_offloaded"] = int(self.stats.bytes_offloaded)
            rec["bytes_loaded"] = int(self.stats.bytes_loaded)
            rec["bytes_forwarded"] = int(self.stats.bytes_forwarded)
            rec["fetch_wait_s"] = float(self.stats.fetch_wait_time)
        if self.plan is not None:
            rec["plan_last_offloaded"] = int(self.plan.last_offloaded)
        if self.obs:
            for k, v in self.obs.items():
                rec[f"obs_{k}"] = v
        if self.shard_stats:
            rec["shards"] = self.shard_stats
        if self.cache:
            for k, v in self.cache.items():
                rec[f"cache_{k}"] = v
        if self.resilience is not None:
            for k, v in self.resilience.items():
                rec[f"resilience_{k}"] = v
        for k, v in self.extra.items():
            rec.setdefault(k, v)
        return rec
