"""Offload policies: first-class placement objects for the staged engine.

The seed API selected residual placement with a `strategy: str` plus an
`adaptive: bool` flag threaded through `StagedTrainer`. That flag soup is
replaced by `OffloadPolicy` objects — the swappable scheduling seam the
interoperability papers (GreedySnake, 10Cache) argue for: the execution
engine asks the policy two questions and never interprets strings.

    should_offload(stage, profile)   -> spool this stage's residuals?
    on_profile(profiles, bandwidths) -> digest the profiling step
                                        (AdaptivePolicy: compute the plan)

Policies:
  KeepPolicy       residuals stay on device (the ROK "K" axis)
  SpoolPolicy      offload every eligible stage unconditionally ("O")
  RecomputePolicy  layerwise recomputation; only module inputs kept ("R")
  AdaptivePolicy   paper §3.3.3: profile step 0, then offload only the
                   prefix the measured store bandwidth can hide

`resolve_policy` maps the legacy surface (strategy strings, adaptive
flag) onto these objects so seed call shapes keep working.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple, Union

from repro.core.adaptive import (BWD_FACTOR, BandwidthLike, ModuleProfile,
                                 OffloadPlan, TierBandwidth, plan_offload)

#: stage roles whose backward can be recomputed from the module input
RECOMPUTABLE_ROLES = ("layer", "enc_layer")


def _scale_bandwidths(bw: BandwidthLike, scale: float) -> BandwidthLike:
    """Bandwidths as the planner should see them after a health event:
    every tier's write rate scaled by `scale` (0.0 = device gone)."""
    if isinstance(bw, (int, float)):
        return float(bw) * scale
    return [TierBandwidth(t.name, t.write_bw * scale, t.capacity_bytes)
            for t in bw]


def _is_decoder_layer(name: str) -> bool:
    """Staged-engine stage names: decoder layers are 'seg{si}_l{rep}'."""
    return name.startswith("seg") and "_l" in name


@dataclass(frozen=True)
class JitOffloadPlan:
    """A profiled plan translated for the jit engine: per-decoder-layer
    keep/offload choices for the repro.core.hooks spool path, derived
    from the same `on_profile` data that drives the staged engine.

    `spool_stages[i]` is True when decoder layer i's residuals should
    stream through the spool; False keeps them on device (matching the
    staged AdaptivePolicy's keep-set). `activation_policy` is what
    `RunSettings.activation_policy` should be — "spool" while any layer
    offloads, else "keep" (nothing to stream)."""

    spool_stages: Tuple[bool, ...]
    activation_policy: str                     # "spool" | "keep"
    required_bw: float
    write_bw: float
    #: fraction of each layer's profiled bytes the planned shard hands
    #: the spool (1.0 = unsharded; see local_shard_fraction)
    shard_fraction: float = 1.0

    def apply(self, settings) -> "RunSettings":  # noqa: F821
        """The same RunSettings with this plan's placement choices."""
        import dataclasses
        return dataclasses.replace(
            settings,
            activation_policy=self.activation_policy,
            spool_stages=(self.spool_stages
                          if self.activation_policy == "spool" else None))


class OffloadPolicy:
    """Base policy: decides, per stage, where residuals live.

    Subclasses override `should_offload` (and, for profile-driven
    policies, `wants_profile` + `on_profile`). `strategy` is the legacy
    string the policy corresponds to — kept so reports, benchmarks and
    CLI output stay stable across the API redesign.
    """

    strategy = "offload"

    #: engine runs a profiling step (warm re-run + wait_io + calibrate)
    #: while this is True
    wants_profile = False

    plan: Optional[OffloadPlan] = None

    def recomputes(self, role: str) -> bool:
        """True if this stage's backward should re-run forward instead of
        saving residuals."""
        return False

    def should_offload(self, stage: int,
                       profile: Optional[ModuleProfile] = None) -> bool:
        raise NotImplementedError

    def on_profile(self, profiles: Sequence[ModuleProfile],
                   bandwidths: BandwidthLike) -> Optional[OffloadPlan]:
        """Digest the profiling step. Returns the plan (or None when the
        policy is static)."""
        return None

    def __repr__(self):
        return f"{type(self).__name__}()"


class KeepPolicy(OffloadPolicy):
    """All residuals stay in device memory (tracked for the footprint
    curve, never written)."""

    strategy = "keep"

    def should_offload(self, stage, profile=None) -> bool:
        return False


class SpoolPolicy(OffloadPolicy):
    """Unconditional TBA: every eligible stage's residuals go to the
    spool (the non-adaptive `strategy="offload", adaptive=False` form)."""

    strategy = "offload"

    def should_offload(self, stage, profile=None) -> bool:
        return True


class RecomputePolicy(OffloadPolicy):
    """Layerwise full recomputation: layer stages keep only their input
    and re-run forward during backward; non-layer stages keep residuals
    on device."""

    strategy = "recompute"

    def recomputes(self, role: str) -> bool:
        return role in RECOMPUTABLE_ROLES

    def should_offload(self, stage, profile=None) -> bool:
        return False


class AdaptivePolicy(OffloadPolicy):
    """Paper §3.3.3: offload everything during the profiling step, then
    plan the largest offloaded prefix whose transfer deadline the
    measured (per-tier) store bandwidth can hold."""

    strategy = "offload"

    def __init__(self, *, bwd_factor: float = BWD_FACTOR,
                 always_keep_last: bool = True,
                 opt_bytes_per_step: int = 0):
        self.bwd_factor = bwd_factor
        self.always_keep_last = always_keep_last
        # opt-overlap moment traffic sharing the write path (see
        # price_opt_io); 0 = no optimizer I/O competing for bandwidth
        self.opt_bytes_per_step = int(opt_bytes_per_step)
        self.plan = None
        self.profiles: Optional[List[ModuleProfile]] = None
        self.bandwidths: Optional[BandwidthLike] = None
        self.cache_manager = None
        # mid-run re-plans triggered by backend health events
        self.replans = 0
        self.last_health_event = None
        import threading as _threading
        self._replan_lock = _threading.Lock()

    def attach_cache_manager(self, manager) -> None:
        """Connect a `repro.cache.CacheManager` backend: after the
        profiling step, the policy converts its measured step timing
        into the manager's per-class reuse distances, so tier placement
        and the offload plan derive from the same profile."""
        self.cache_manager = manager

    def price_opt_io(self, bytes_per_step: int) -> None:
        """Account for the opt-overlap bridge's moment traffic: the
        bridge stages ~`bytes_per_step` of optimizer state through the
        same write path every step, so the activation deadline test must
        plan against the leftover bandwidth, not the raw tier rate.
        Re-plans immediately when a profile is already in hand."""
        with self._replan_lock:
            self.opt_bytes_per_step = int(bytes_per_step)
            if self.profiles is None or self.bandwidths is None:
                return      # priced at on_profile time instead
            self.plan = plan_offload(
                self.profiles, self._priced(self.bandwidths),
                bwd_factor=self.bwd_factor,
                always_keep_last=self.always_keep_last)
            self.replans += 1

    def _priced(self, bandwidths: BandwidthLike) -> BandwidthLike:
        """`bandwidths` minus the opt-state write rate. The moment
        writer moves opt_bytes_per_step over one step, so it claims
        bytes/t_step of write bandwidth; floor at 1 B/s so a saturated
        tier degrades the plan instead of crashing the divide."""
        if self.opt_bytes_per_step <= 0 or not self.profiles:
            return bandwidths
        t_step = sum(p.fwd_time for p in self.profiles) \
            * (1.0 + self.bwd_factor)
        if t_step <= 0:
            return bandwidths
        rate = self.opt_bytes_per_step / t_step
        if isinstance(bandwidths, (int, float)):
            return max(float(bandwidths) - rate, 1.0)
        return [TierBandwidth(t.name, max(t.write_bw - rate, 1.0),
                              t.capacity_bytes)
                for t in bandwidths]

    def attach_health(self, health) -> None:
        """Subscribe to a `repro.resilience.BackendHealth` monitor: on
        a degrade/failing/recovered transition the policy re-plans
        against the bandwidth the backend can still deliver (failing →
        nothing offloads; stages degrade to on-device residuals, and
        already-offloaded ones ride the engines' recompute fallback).
        Tier demotion inside a managed backend needs no action here —
        the `CacheManager.fallback_to_upper` path already re-homes
        blobs when the SSD tier errors, and its fallback counters ride
        the cache_* metrics block."""
        health.subscribe(self.on_health_event)

    def on_health_event(self, event) -> None:
        """Re-plan mid-run from an I/O-worker thread. Cheap and
        lock-protected: compute a new plan from the retained profile
        with the degraded bandwidth, then swap the plan reference (the
        engine reads it between stages)."""
        from repro import obs
        with self._replan_lock:
            self.last_health_event = event
            if self.profiles is None or self.bandwidths is None:
                return      # no profile yet: nothing to re-plan from
            if event.kind == "failing":
                scale = 0.0  # device gone: stop offloading entirely
            elif event.kind == "degraded":
                scale = 1.0 / max(event.latency_ratio, 1.0)
            else:            # recovered
                scale = 1.0
            self.plan = plan_offload(
                self.profiles,
                self._priced(_scale_bandwidths(self.bandwidths, scale)),
                bwd_factor=self.bwd_factor,
                always_keep_last=self.always_keep_last)
            self.replans += 1
            n_off = sum(self.plan.offload)
        if obs.is_enabled():
            obs.instant("resilience.replan", cat="resilience",
                        trigger=event.kind, op=event.op,
                        bw_scale=round(scale, 4),
                        stages_offloaded=n_off,
                        latency_ratio=round(event.latency_ratio, 3))

    @property
    def wants_profile(self) -> bool:
        return self.plan is None

    def should_offload(self, stage, profile=None) -> bool:
        if self.plan is None:
            return True      # profiling step offloads everything it can
        return self.plan.offload[stage]

    def on_profile(self, profiles, bandwidths) -> OffloadPlan:
        self.profiles = list(profiles)
        self.bandwidths = bandwidths
        self.plan = plan_offload(self.profiles, self._priced(bandwidths),
                                 bwd_factor=self.bwd_factor,
                                 always_keep_last=self.always_keep_last)
        if self.cache_manager is not None:
            # Measured reuse distances in seconds, one consistent unit:
            # a residual's mean wait until backward is ~half a step, an
            # optimizer moment waits a full step (step parity), and a
            # parked KV sequence is rescaled to keep its default 3x rank
            # (serving measures its own recency when it runs).
            t_step = sum(p.fwd_time for p in self.profiles) \
                * (1.0 + self.bwd_factor)
            if t_step > 0:
                self.cache_manager.hint_class_distance(
                    "activation", 0.5 * t_step)
                self.cache_manager.hint_class_distance(
                    "opt_state", t_step)
                self.cache_manager.hint_class_distance(
                    "kv_page", 3.0 * t_step)
        return self.plan

    def plan_for_jit(self, *, shard_fraction: float = 1.0) \
            -> JitOffloadPlan:
        """The profiled plan as per-decoder-layer placement for the jit
        engine's hook path — one policy object, profiled once (on either
        engine), drives both step-execution modes.

        `shard_fraction` scales the profiled per-layer byte estimates
        before planning: on an SPMD mesh every shard spools only its
        local residual block (batch-dim sharding over the dp axes), so
        the deadline feasibility test should judge local bytes, not the
        single-device profile's global ones. Use `local_shard_fraction`
        for the fraction a given mesh implies; a smaller fraction can
        only offload MORE layers."""
        if self.plan is None or self.profiles is None:
            raise RuntimeError(
                "plan_for_jit() needs a profiling step first: run one "
                "staged step with this policy (on_profile) before "
                "translating the plan for the jit engine")
        if not 0.0 < shard_fraction <= 1.0:
            raise ValueError(
                f"shard_fraction must be in (0, 1], got {shard_fraction}")
        plan = self.plan
        if shard_fraction != 1.0:
            scaled = [ModuleProfile(p.name,
                                    int(round(p.bytes * shard_fraction)),
                                    p.fwd_time)
                      for p in self.profiles]
            plan = plan_offload(scaled, self._priced(self.bandwidths),
                                bwd_factor=self.bwd_factor,
                                always_keep_last=self.always_keep_last)
        mask = tuple(bool(off)
                     for prof, off in zip(self.profiles, plan.offload)
                     if _is_decoder_layer(prof.name))
        return JitOffloadPlan(
            spool_stages=mask,
            activation_policy="spool" if any(mask) else "keep",
            required_bw=plan.required_bw,
            write_bw=plan.write_bw,
            shard_fraction=shard_fraction)

    def __repr__(self):
        return (f"AdaptivePolicy(bwd_factor={self.bwd_factor}, "
                f"planned={self.plan is not None})")


def local_shard_fraction(mesh, dp_axes=("data",)) -> float:
    """Fraction of a hooked layer's residual bytes ONE shard hands the
    spool under the sharded offload hooks: the leading (batch) dim
    splits over the dp axes, so each shard holds 1/dp_size of a
    batch-major residual (tp slices shrink per-device bytes further but
    also multiply writers, leaving per-host totals unchanged — dp is
    the term that scales a shard's transfer deadline)."""
    if mesh is None:
        return 1.0
    n = 1
    for a in (dp_axes or ()):
        if a in mesh.shape:
            n *= int(mesh.shape[a])
    return 1.0 / max(n, 1)


#: what the legacy strategy strings resolve to
_STRATEGIES = ("keep", "offload", "recompute", "adaptive", "spool")


def resolve_policy(policy: Union[OffloadPolicy, str, None] = None, *,
                   strategy: Optional[str] = None,
                   adaptive: Optional[bool] = None) -> OffloadPolicy:
    """One resolver for every call shape.

    New API: pass an `OffloadPolicy` (or its name: "keep" / "offload" /
    "recompute" / "adaptive" / "spool"). Legacy shim: `strategy=` +
    `adaptive=` keyword pair, with the seed defaults (offload,
    adaptive=True) when everything is None. A bare "offload" keeps the
    seed meaning — adaptive unless `adaptive=False` is passed.
    """
    if policy is not None and (strategy is not None or adaptive is not None):
        raise ValueError("pass either policy= or the legacy "
                         "strategy=/adaptive= pair, not both")
    if isinstance(policy, OffloadPolicy):
        return policy
    name = policy if policy is not None else strategy
    if name is None:
        name = "offload"
    if not isinstance(name, str) or name not in _STRATEGIES:
        raise ValueError(f"unknown offload policy {name!r}; expected an "
                         f"OffloadPolicy or one of {_STRATEGIES}")
    if name == "keep":
        return KeepPolicy()
    if name == "recompute":
        return RecomputePolicy()
    if name == "spool":
        return SpoolPolicy()
    if name == "adaptive":
        return AdaptivePolicy()
    # "offload": seed semantics — adaptive unless explicitly disabled
    return SpoolPolicy() if adaptive is False else AdaptivePolicy()
