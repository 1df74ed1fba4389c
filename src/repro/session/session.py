"""TrainSession — one front door for both training engines.

The paper's interoperability claim (SSDTrain plugs into any framework
behind one hook-based API) maps here to a single facade that owns:

  * config resolution      — arch strings ("small-gpt", "qwen2.5-3b:reduced",
                             "gpt-h256-l4") or a ModelConfig
  * engine selection       — "staged" (per-module TBA path, real spool I/O)
                             or "jit" (whole-step XLA, fault-tolerant loop)
  * placement policy       — an `OffloadPolicy` object (staged engine)
  * the ActivationSpool    — built from one `SpoolIoConfig` for EITHER
                             engine: the staged engine spools per-module
                             residuals; the jit engine stages optimizer
                             state between steps
                             (`io.host_offload="opt_state"`) or streams
                             per-layer residuals from inside the jitted
                             step through repro.core.hooks
                             (`io.host_offload="activations"`)
  * checkpointing          — periodic async checkpoints + resume
  * metrics                — one unified `StepReport` stream / JSONL
                             schema regardless of engine

    with TrainSession("small-gpt", engine="staged",
                      policy=AdaptivePolicy()) as sess:
        result = sess.run(100)
    print(result.final_loss)
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Union

import numpy as np

import jax

from repro import obs
from repro.ckpt.checkpoint import (CheckpointManager, restore_train_state,
                                   save_train_state)
from repro.configs import ARCH_IDS, get_config, reduced
from repro.configs.base import ModelConfig, SpoolIoConfig
from repro.configs.paper_models import gpt, small_bert, small_gpt
from repro.core.policies import OffloadPolicy, resolve_policy
from repro.core.report import StepReport
from repro.core.spool import build_spool
from repro.core.staged import StagedTrainer
from repro.data.pipeline import ShardedLoader, SyntheticMarkovLM
from repro.launch.steps import make_host_train_step
from repro.models.api import build_model
from repro.models.transformer import RunSettings
from repro.parallel.sharding import (MeshAxes, param_specs,
                                     spec_tree_for_optstate)
from repro.optim.optimizers import Optimizer, adamw, sgd
from repro.runtime.trainer import (StragglerWatchdog, TrainLoop,
                                   TrainState, batch_tokens)

ENGINES = ("staged", "jit")


def resolve_config(name: str) -> ModelConfig:
    """Arch string -> ModelConfig. Accepts: assigned ids, '<id>:reduced',
    gpt-124m, small-gpt/small-bert, or gpt-h<H>-l<L>."""
    if name == "gpt-124m":
        return dataclasses.replace(
            gpt(768, 12, vocab=32768), num_heads=12, num_kv_heads=12,
            head_dim=64)
    if name == "small-gpt":
        return small_gpt()
    if name == "small-bert":
        return small_bert()
    if name.endswith(":reduced"):
        return reduced(get_config(name[:-len(":reduced")]))
    if name in ARCH_IDS:
        return get_config(name)
    if name.startswith("gpt-h"):
        h, l = name[5:].split("-l")
        return gpt(int(h), int(l))
    raise ValueError(f"unknown arch {name!r}")


def _resolve_optimizer(optimizer: Union[str, Optimizer],
                       lr: float) -> Optimizer:
    if isinstance(optimizer, Optimizer):
        return optimizer
    if optimizer == "adamw":
        return adamw(lr)
    if optimizer == "sgd":
        return sgd(lr)
    raise ValueError(f"unknown optimizer {optimizer!r}")


# one throughput rule for both engines (labels >= 0 are real targets)
_batch_tokens = batch_tokens


@dataclass
class SessionResult:
    """What a `TrainSession.run` hands back."""
    engine: str
    state: TrainState
    reports: List[StepReport] = field(default_factory=list)

    @property
    def losses(self) -> List[float]:
        return [r.loss for r in self.reports]

    @property
    def final_loss(self) -> float:
        return self.reports[-1].loss if self.reports else float("nan")


class TrainSession:
    """Facade over the staged (TBA) and jit engines; see module docstring.

    Every knob that used to be an engine-specific kwarg is one argument
    here, interpreted identically for both engines wherever it applies.
    """

    def __init__(self, arch: Union[str, ModelConfig] = "small-gpt", *,
                 engine: str = "staged",
                 policy: Union[OffloadPolicy, str, None] = None,
                 io: Optional[SpoolIoConfig] = None,
                 optimizer: Union[str, Optimizer] = "adamw",
                 lr: float = 3e-4,
                 batch_size: int = 8, seq_len: int = 256,
                 seed: int = 0, microbatches: int = 1,
                 settings: Optional[RunSettings] = None,
                 mesh: Any = None,
                 mesh_axes: Optional[MeshAxes] = None,
                 loader: Any = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 keep_last: int = 3,
                 metrics_path: Optional[str] = None,
                 spool_dir: Optional[str] = None,
                 min_offload_elements: Optional[int] = None,
                 trace: Optional[str] = None,
                 trace_ring: int = 0,
                 opt_overlap: Union[bool, str, None] = None,
                 install_signal_handlers: bool = False):
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r}; "
                             f"expected one of {ENGINES}")
        if engine == "jit" and policy is not None:
            raise ValueError(
                "OffloadPolicy applies to the staged engine; the jit "
                "engine fixes activation placement at trace time "
                "(RunSettings.activation_policy) and uses "
                "io.host_offload ('opt_state' between-step staging or "
                "'activations' per-layer hooks). To drive the jit "
                "engine from a profiled AdaptivePolicy, pass "
                "settings=policy.plan_for_jit().apply(settings)")
        if mesh is not None and engine != "jit":
            raise ValueError(
                "mesh-sharded training is a jit-engine feature; the "
                "staged engine runs per-module jit calls on one device")
        self.engine = engine
        self.mesh = mesh
        self.mesh_axes = None
        if mesh is not None:
            self.mesh_axes = mesh_axes or MeshAxes(
                dp=tuple(a for a in mesh.axis_names if a != "model"),
                tp=("model" if "model" in mesh.axis_names else None))
        self.cfg = (resolve_config(arch) if isinstance(arch, str)
                    else arch.validate())
        self.io = io.validate() if io is not None else None
        # eager per-layer optimizer overlap (repro.optim.overlap):
        # session kwarg wins, else the io config's knob. Truthy values:
        # True (overlapped worker) or "sync" (same kernels/taps, updates
        # applied in finish_step — the same-compile serial reference).
        if opt_overlap is None:
            opt_overlap = (self.io.opt_overlap
                           if self.io is not None else False)
        self.opt_overlap = opt_overlap
        if opt_overlap and engine != "jit":
            raise ValueError("opt_overlap is a jit-engine feature (the "
                             "staged engine already updates per stage)")
        self.api = build_model(self.cfg)
        self.optimizer = _resolve_optimizer(optimizer, lr)
        self.seed = seed
        self.microbatches = microbatches
        self.metrics_path = metrics_path
        self.ckpt_every = ckpt_every
        self.keep_last = keep_last
        self.install_signal_handlers = install_signal_handlers
        self.reports: List[StepReport] = []
        self._metrics_f = None
        self._state: Optional[TrainState] = None
        self._loop: Optional[TrainLoop] = None
        self._owned_tmpdirs: List[str] = []
        self._closed = False

        # repro.obs: trace export path + whether this session installed
        # the process tracer (and so must tear it down). The per-step
        # snapshot state feeds _step_deltas() so metrics rows are
        # per-step, not run-cumulative.
        self.trace_path = trace
        self._owns_tracer = False
        self._tracer = None
        if trace is not None or trace_ring:
            self._owns_tracer = not obs.is_enabled()
            self._tracer = obs.enable(trace_ring or obs.DEFAULT_RING_SIZE)
        self._stats_snapshot = None
        self._shard_snapshot: dict = {}
        self._obs_cursor = None
        self._counters_snapshot: dict = {}
        self._cache_snapshot = None
        self._resil_snapshot: dict = {}

        if loader is None:
            loader = ShardedLoader(
                SyntheticMarkovLM(self.cfg.vocab_size, seed=seed),
                global_batch=batch_size, seq_len=seq_len)
        self.loader = loader
        self._loader_iter = None

        if ckpt_dir is None:
            # the jit engine's TrainLoop always commits a final
            # checkpoint; park it somewhere we clean up
            ckpt_dir = tempfile.mkdtemp(prefix="session_ckpt_")
            self._owned_tmpdirs.append(ckpt_dir)
        self.ckpt_dir = ckpt_dir

        self._hook_bridge = None
        self._opt_bridge = None
        self._optb_snapshot: dict = {}
        if engine == "staged":
            self.policy = resolve_policy(policy)
            self.settings = settings or RunSettings(
                attn_impl="xla", attn_chunk=256,
                param_dtype=self.cfg.dtype)
            self.trainer = StagedTrainer(
                self.api, self.settings, self.optimizer,
                policy=self.policy, io_config=self.io,
                spool_dir=spool_dir,
                num_microbatches=microbatches,
                min_offload_elements=min_offload_elements)
            self.spool = self.trainer.spool
            self._ckpt = CheckpointManager(ckpt_dir, keep_last=keep_last)
        else:
            self.policy = None
            self.trainer = None
            self._ckpt = None       # TrainLoop owns its manager
            mode = self.io.host_offload if self.io is not None else "none"
            self.spool = None
            if mode != "none" or self.opt_overlap:
                # opt overlap needs a spool even when no host_offload
                # mode is set — the per-layer moment leases live on it
                self.spool, owned = build_spool(
                    self.io, spool_dir=spool_dir,
                    min_offload_elements=min_offload_elements)
                self._owned_tmpdirs += owned
            if mode == "activations" and settings is not None \
                    and settings.activation_policy != "spool":
                raise ValueError(
                    "io.host_offload='activations' requires "
                    "settings.activation_policy='spool' (got "
                    f"{settings.activation_policy!r}); either drop the "
                    "'activations' mode or let the session synthesize "
                    "the settings. A JitOffloadPlan that kept every "
                    "layer on device (activation_policy='keep') needs "
                    "no spool — run without host_offload='activations'")
            self.settings = settings or RunSettings(
                attn_impl="xla", attn_chunk=256,
                activation_policy=("spool" if mode == "activations"
                                   else "remat"),
                param_dtype=self.cfg.dtype)
            if self.mesh is not None and self.settings.mesh is None:
                # user settings (or the synthesized defaults) predate
                # the mesh choice: fill in the sharding hints so the
                # model partitions and the hooks see the mesh
                self.settings = dataclasses.replace(
                    self.settings, mesh=self.mesh,
                    tp_axis=self.mesh_axes.tp,
                    dp_axes=self.mesh_axes.dp)
            if mode == "activations" \
                    and self.settings.activation_policy == "spool":
                # per-layer residual streaming: the hooks inside the
                # jitted step talk to the spool through this bridge
                from repro.core.hooks import HookBridge
                self._hook_bridge = HookBridge(
                    self.spool,
                    dedupe_replicas=(self.io.dedupe_replicas
                                     if self.io is not None else True),
                    fetch_fallback=(
                        getattr(self.io, "on_fetch_fail", "recompute")
                        == "recompute" if self.io is not None else True))
                self.settings = dataclasses.replace(
                    self.settings, hook_bridge=self._hook_bridge)
            if self.opt_overlap:
                from repro.launch.steps import make_overlap_train_step
                from repro.optim.overlap import OptBridge
                self._opt_bridge = OptBridge(
                    self.optimizer, self.spool,
                    eager=(self.opt_overlap != "sync"))
                self.settings = dataclasses.replace(
                    self.settings, opt_sink=self._opt_bridge)
                self._step_fn = make_overlap_train_step(
                    self.api, self.optimizer, self.settings,
                    self._opt_bridge, mesh=self.mesh,
                    axes=self.mesh_axes)
            else:
                self._step_fn = make_host_train_step(
                    self.api, self.optimizer, self.settings,
                    mesh=self.mesh, axes=self.mesh_axes,
                    donate_opt_state=(mode != "opt_state"))

    # ------------------------------------------------------------ state

    def init(self) -> TrainState:
        """Initialise (or return the current) model/optimizer state.
        With a mesh, params are placed with the production sharding
        rules (fsdp+tp) and the optimizer state inherits them (ZeRO);
        the step counter replicates."""
        if self._state is None:
            params = self.api.init(jax.random.key(self.seed))
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                p_specs = param_specs(self.cfg, params, self.mesh,
                                      self.mesh_axes, fsdp=True)
                as_sh = lambda s: NamedSharding(self.mesh, s)  # noqa: E731
                params = jax.device_put(
                    params, jax.tree.map(
                        as_sh, p_specs,
                        is_leaf=lambda x: isinstance(x, P)))
                opt_state = self.optimizer.init(params)
                o_specs = spec_tree_for_optstate(p_specs, opt_state)
                opt_state = jax.device_put(
                    opt_state, jax.tree.map(
                        as_sh, o_specs,
                        is_leaf=lambda x: isinstance(x, P)))
            else:
                opt_state = self.optimizer.init(params)
            self._state = TrainState(0, params, opt_state)
        return self._state

    @property
    def state(self) -> Optional[TrainState]:
        return self._state

    @property
    def n_params(self) -> int:
        return sum(x.size for x in jax.tree.leaves(self.init().params))

    @property
    def watchdog(self) -> Optional[StragglerWatchdog]:
        return self._loop.watchdog if self._loop is not None else None

    # ------------------------------------------------------------- run

    def run(self, num_steps: int, *, resume: bool = False,
            on_report: Optional[Callable[[StepReport], None]] = None) \
            -> SessionResult:
        """Train for `num_steps` optimizer steps; returns the final
        state plus the unified per-step reports."""
        if self._closed:
            raise RuntimeError("session is closed")
        self.init()
        start = len(self.reports)   # result carries THIS run's reports
        if self.engine == "staged":
            self._run_staged(num_steps, resume=resume,
                             on_report=on_report)
        else:
            self._run_jit(num_steps, resume=resume, on_report=on_report)
        return SessionResult(self.engine, self._state,
                             list(self.reports[start:]))

    def _step_deltas(self):
        """Per-step observability snapshot-and-diff, called once at each
        step boundary: spool stats delta (fixes the old cumulative-in-
        JSONL rows), per-shard HookBridge traffic delta, and the overlap
        analysis of this step's (incremental) trace window."""
        stats_delta = None
        if self.spool is not None:
            cur = self.spool.stats.snapshot()
            prev = self._stats_snapshot
            stats_delta = cur.sub(prev) if prev is not None else cur
            self._stats_snapshot = cur
        shard_delta = None
        if self._hook_bridge is not None:
            cur_sh = self._hook_bridge.stats_by_shard()
            prev_sh = self._shard_snapshot
            shard_delta = {}
            for shard, rec in cur_sh.items():
                prev_rec = prev_sh.get(shard, {})
                d = {k: v - prev_rec.get(k, 0) for k, v in rec.items()}
                if any(d.values()):
                    name = "global" if shard is None else str(shard)
                    shard_delta[name] = d
            self._shard_snapshot = cur_sh
        obs_delta = None
        tracer = obs.get_tracer()
        if tracer is not None:
            from repro.obs import overlap
            events, self._obs_cursor = tracer.snapshot_new(
                self._obs_cursor)
            counters = tracer.counters()
            prev_c = self._counters_snapshot
            delta_c = {k: v - prev_c.get(k, 0)
                       for k, v in counters.items()}
            self._counters_snapshot = counters
            obs_delta = overlap.analyze(events, delta_c)
        cache_delta = None
        cm = getattr(self.spool, "cache_manager", None) \
            if self.spool is not None else None
        if cm is not None:
            cache_delta, self._cache_snapshot = \
                cm.metrics_delta(self._cache_snapshot)
        resil_delta = self._resilience_delta()
        return (stats_delta, shard_delta, obs_delta, cache_delta,
                resil_delta)

    #: resilience counters that grow monotonically and are emitted as
    #: per-step differences (gauges like health ride along un-diffed)
    _RESIL_MONOTONIC = ("store_retries", "load_retries",
                        "fetch_fallbacks", "replans",
                        "rebalanced_chunks", "chunk_write_failures")

    def _resilience_delta(self):
        """Per-step resilience block: retry / fallback / re-plan /
        rebalance counter deltas plus current backend-health gauges.
        Present on every step that has a spool (zeros on healthy runs),
        so consumers can rely on the columns existing."""
        if self.spool is None:
            return None
        from repro.resilience import unwrap_chain
        cur: dict = {}
        st = self.spool.stats
        cur["store_retries"] = st.store_retries
        cur["load_retries"] = st.load_retries
        cur["fetch_fallbacks"] = st.fetch_fallbacks
        if self.policy is not None and hasattr(self.policy, "replans"):
            cur["replans"] = self.policy.replans
        for b in unwrap_chain(self.spool.backend):
            if hasattr(b, "rebalanced_chunks"):
                cur["rebalanced_chunks"] = b.rebalanced_chunks
                cur["chunk_write_failures"] = b.chunk_write_failures
                break
        prev = self._resil_snapshot
        delta = {k: v - prev.get(k, 0) for k, v in cur.items()
                 if k in self._RESIL_MONOTONIC}
        self._resil_snapshot = cur
        health = getattr(self.spool, "health", None)
        if health is not None:
            delta["health"] = health.snapshot()["health"]
        for b in unwrap_chain(self.spool.backend):
            if hasattr(b, "devices_down"):
                delta["devices_down"] = sum(b.devices_down())
                break
        return delta

    def _emit(self, rep: StepReport,
              on_report: Optional[Callable]) -> None:
        self.reports.append(rep)
        if self.metrics_path:
            if self._metrics_f is None:
                self._metrics_f = open(self.metrics_path, "a")
            self._metrics_f.write(json.dumps(rep.to_metrics()) + "\n")
            self._metrics_f.flush()
        if on_report:
            on_report(rep)

    # ---------------------------------------------------- staged engine

    def _staged_resume(self) -> bool:
        restored = restore_train_state(
            self._ckpt, self._state.params, self._state.opt_state,
            self.loader)
        if restored is None:
            return False
        self._state = TrainState(*restored)
        return True

    def _staged_save(self, final: bool = False) -> None:
        save_train_state(self._ckpt, self._state.step,
                         self._state.params, self._state.opt_state,
                         self.loader, final=final)

    def _run_staged(self, num_steps, *, resume, on_report):
        if resume:
            self._staged_resume()
        if self._loader_iter is None:
            self._loader_iter = iter(self.loader)
        params, opt_state = self._state.params, self._state.opt_state
        step = self._state.step
        for _ in range(num_steps):
            batches = [next(self._loader_iter)
                       for _ in range(self.microbatches)]
            params, opt_state, rep = self.trainer.train_step(
                params, opt_state, batches)
            step += 1
            rep.step = step
            (rep.stats, rep.shard_stats, rep.obs, rep.cache,
             rep.resilience) = self._step_deltas()
            tokens = sum(_batch_tokens(b) for b in batches)
            rep.tokens_per_s = tokens / rep.step_time \
                if rep.step_time else 0.0
            self._state = TrainState(step, params, opt_state)
            self._emit(rep, on_report)
            if self.ckpt_every and step % self.ckpt_every == 0:
                self._staged_save()
        self._staged_save(final=True)

    # ------------------------------------------------------- jit engine

    def _run_jit(self, num_steps, *, resume, on_report):
        def on_step(step, dt, metrics, batch):
            tokens = _batch_tokens(batch)
            extra = {}
            for k, v in (metrics or {}).items():
                try:
                    extra[k] = float(v)
                except (TypeError, ValueError):
                    pass
            stats_d, shard_d, obs_d, cache_d, resil_d = \
                self._step_deltas()
            if self._opt_bridge is not None:
                cur = self._opt_bridge.stats()
                prev = self._optb_snapshot
                extra.update({k: cur[k] - prev.get(k, 0) for k in cur})
                self._optb_snapshot = cur
            rep = StepReport(
                loss=extra.get("loss", float("nan")),
                step_time=dt, step=step, engine="jit",
                stats=stats_d,
                tokens_per_s=tokens / dt if dt else 0.0,
                extra=extra, obs=obs_d, shard_stats=shard_d,
                cache=cache_d, resilience=resil_d)
            self._emit(rep, on_report)

        if self._loop is None:
            self._loop = TrainLoop(
                step_fn=self._step_fn, init_state=self._state,
                loader=self.loader, ckpt_dir=self.ckpt_dir,
                ckpt_every=self.ckpt_every, keep_last=self.keep_last,
                watchdog=StragglerWatchdog(),
                spool=self.spool,
                host_offload=(self.io.host_offload
                              if self.io is not None else "none"),
                opt_bridge=self._opt_bridge,
                install_signal_handlers=self.install_signal_handlers)
        self._loop.on_step = on_step
        self._loop.state = self._state
        if resume:
            self._loop.resume()
        self._state = self._loop.run(num_steps)

    # ----------------------------------------------------------- close

    def close(self) -> None:
        """Idempotent teardown: engines, spool, metrics file, and any
        temp directories this session created."""
        if self._closed:
            return
        self._closed = True
        if self.trainer is not None:
            self.trainer.close()
        if self._loop is not None:
            self._loop.close()
        if self._hook_bridge is not None:
            self._hook_bridge.close()      # drop aborted-step leases
        if self._opt_bridge is not None:
            self._opt_bridge.close()       # stop worker, drop moment leases
        if self.engine == "jit" and self.spool is not None:
            self.spool.close()
        if self._ckpt is not None:
            self._ckpt.wait()
        if self._metrics_f is not None:
            self._metrics_f.close()
        # export the trace after every engine/spool quiesced, so the
        # timeline is complete and all spans are closed
        if self._tracer is not None and self.trace_path:
            from repro.obs.export import write_chrome_trace
            write_chrome_trace(self.trace_path, self._tracer,
                               extra={"engine": self.engine,
                                      "arch": self.cfg.name})
        if self._owns_tracer:
            obs.disable()
        for d in self._owned_tmpdirs:
            shutil.rmtree(d, ignore_errors=True)

    def __enter__(self) -> "TrainSession":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
