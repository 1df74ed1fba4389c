"""Host-staged trainer: the runnable TBA path (paper §3.1–§3.3).

Executes a training step as a chain of jitted per-module stages
(encoder stages -> embed -> super-layer x L -> loss head). After each
module's forward, its *actual autograd residuals* — the tensors jax.vjp
saves for backward, extracted by flattening the vjp closure — are handed
to the ActivationSpool, which stores them asynchronously; backward walks
the chain in reverse, prefetching one module ahead. This is the
pack/unpack-hook dataflow of the paper realised JAX-natively:

  pack hook      -> vjp-residual extraction + spool.offload()
  unpack hook    -> spool.fetch() (blocking, with tensor forwarding)
  param exclusion-> trace-time tracer-identity detection of parameter
                    leaves (paper §3.3.1)
  scope stack    -> the explicit stage list
  backward prefetch (§3.3.2) -> spool.prefetch(prev stage)
  adaptive offloading (§3.3.3) -> profile step 0, plan_offload(), keep-set

Encoder-decoder (T5) and VLM archs thread a second value — the encoder
states `enc` — through the chain: every cross-attention stage consumes
it, and its cotangents accumulate across stages before flowing back into
the encoder stages (`enc` is referenced by many scopes but offloaded
once — the paper's §3.3.1 dedup scenario).

Residual placement is decided by an `OffloadPolicy` object
(`repro.core.policies`, re-exported by `repro.session`) — KeepPolicy /
SpoolPolicy / RecomputePolicy / AdaptivePolicy are the ROK axes of §4.3.
The legacy `strategy: str` + `adaptive: bool` kwargs still work as a
deprecation shim via `resolve_policy`.

Spool access goes through transactional step leases
(`spool.step(step_id)`): key construction and drop bookkeeping live in
the transaction, and an exception mid-step drops every still-live
record instead of leaking blobs on the backend.
"""
from __future__ import annotations

import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cache.horizon import reuse_horizon
from repro.core.accounting import MemoryTracker
from repro.core.adaptive import ModuleProfile, OffloadPlan
from repro.core.policies import OffloadPolicy, resolve_policy
from repro.core.report import StepReport
from repro.core.spool import build_spool
from repro.models.api import ModelApi
from repro.models.layers import rms_norm
from repro.models.transformer import RunSettings, apply_block
from repro.optim.optimizers import global_norm


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
               if hasattr(x, "size"))


class _Stage:
    """One module of the chain, with faithful fwd/bwd splitting.

    role: enc_embed | enc_layer | enc_final | vlm_enc | embed | layer
          | head.  takes_enc: stage fn is f(p, x, enc)."""

    def __init__(self, name: str, fn: Callable, role: str,
                 takes_enc: bool = False):
        self.name = name
        self.fn = fn
        self.role = role
        self.takes_enc = takes_enc
        self.cell: Dict[str, Any] = {}

        def fwd(p, *args):
            out, vjp = jax.vjp(fn, p, *args)
            leaves, treedef = jax.tree.flatten(vjp)
            pids = {id(t) for t in jax.tree.leaves(p)}
            self.cell["treedef"] = treedef
            self.cell["param_idx"] = tuple(
                i for i, l in enumerate(leaves) if id(l) in pids)
            self.cell["n_leaves"] = len(leaves)
            return out, tuple(leaves)

        def bwd(leaves, g):
            vjp = jax.tree.unflatten(self.cell["treedef"], list(leaves))
            return vjp(g)

        def bwd_recompute(p, args, g):
            _, vjp = jax.vjp(fn, p, *args)
            return vjp(g)

        self.fwd = jax.jit(fwd)
        self.bwd = jax.jit(bwd)
        self.bwd_recompute = jax.jit(bwd_recompute)

    def split_leaves(self, leaves):
        """(param_leaves_by_idx, residual_leaves_by_idx)"""
        pidx = set(self.cell["param_idx"])
        params = {i: l for i, l in enumerate(leaves) if i in pidx}
        resid = {i: l for i, l in enumerate(leaves) if i not in pidx}
        return params, resid


# Back-compat: StepReport used to be defined here; it now lives in
# repro.core.report as the schema shared by both engines.
__all__ = ["StagedTrainer", "StepReport"]


class StagedTrainer:
    def __init__(self, api: ModelApi, settings: RunSettings, optimizer,
                 *, policy: Optional[OffloadPolicy] = None,
                 strategy: Optional[str] = None,
                 spool_dir: Optional[str] = None,
                 backend=None, io_config=None, codec: Optional[str] = None,
                 store_threads: Optional[int] = None,
                 load_threads: Optional[int] = None,
                 bandwidth_limit: Optional[float] = None,
                 adaptive: Optional[bool] = None,
                 num_microbatches: int = 1,
                 min_offload_elements: Optional[int] = None,
                 on_fetch_fail: Optional[str] = None):
        self.api = api
        self.cfg = api.cfg
        self.settings = settings
        self.optimizer = optimizer
        # `strategy`/`adaptive` are the legacy kwargs; resolve_policy
        # maps them (and the seed defaults) onto a policy object.
        self.policy = resolve_policy(policy, strategy=strategy,
                                     adaptive=adaptive)
        self.strategy = self.policy.strategy      # legacy string view
        self.num_microbatches = num_microbatches
        self.tracker = MemoryTracker()
        self._closed = False
        self.spool, self._owned_tmpdirs = build_spool(
            io_config, backend=backend, spool_dir=spool_dir,
            codec=codec, store_threads=store_threads,
            load_threads=load_threads, bandwidth_limit=bandwidth_limit,
            tracker=self.tracker,
            min_offload_elements=min_offload_elements)
        # Degradation ladder (repro.resilience): when a residual fetch
        # ultimately fails (blob lost, device gone), "recompute" re-runs
        # the stage's forward from a host-RAM copy of its input kept
        # during forward; "raise" keeps the seed behavior (the step
        # dies). The host copy costs RAM, never device memory.
        self.on_fetch_fail = (on_fetch_fail
                              or getattr(io_config, "on_fetch_fail", None)
                              or "recompute")
        assert self.on_fetch_fail in ("recompute", "raise")
        # mid-run re-plan: the policy watches the spool's health monitor
        if hasattr(self.policy, "attach_health"):
            self.policy.attach_health(self.spool.health)
        self._profiles: Optional[List[ModuleProfile]] = None
        self._stages = self._build_stages()
        self._step = 0
        # one jitted update that writes the new state over the old:
        # without donation both copies of params and opt state are live
        self._update = jax.jit(self._update_fn, static_argnums=(3,),
                               donate_argnums=(1, 2))
        self._device = jax.local_devices()[0]
        #: times the forward drained the store queue for device room
        self.device_room_waits = 0

    def _make_device_room(self, need: int) -> None:
        """Wait for the queued stores to land when the device cannot
        take `need` more bytes. A store holds its residuals on the
        device until its write lands, and the forward outruns the store
        path on an accelerator: qwen2.5-3b layers at 4096 tokens emit
        ~2.6 GB of residuals each in milliseconds, and their fs stores
        take seconds, so four layers' worth would not fit a 16 GB chip
        next to the training state. A device that reports no memory
        limit (the CPU) never waits."""
        stats = self._device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return
        if stats["bytes_in_use"] + need > stats["bytes_limit"]:
            self.device_room_waits += 1
            with obs.span("engine.wait_device_room", cat="engine",
                          step=self._step):
                self.spool.wait_io()

    def _update_fn(self, grads, opt_state, params, scale: float):
        grads = jax.tree.map(lambda g: g * scale, grads)
        params, opt_state = self.optimizer.update(grads, opt_state, params)
        return params, opt_state, global_norm(grads)

    @property
    def plan(self) -> Optional[OffloadPlan]:
        return self.policy.plan

    @property
    def adaptive(self) -> bool:
        """Legacy view: is the policy profile-driven?"""
        return self.policy.wants_profile or self.policy.plan is not None

    # ------------------------------------------------------ stage chain

    def _build_stages(self) -> List[_Stage]:
        api, cfg, settings = self.api, self.cfg, self.settings
        stages: List[_Stage] = []

        from repro.models.api import _embed_in, head_ce_terms
        import dataclasses as _dc

        # ---- encoder stream (T5) / stub frontend (VLM)
        if cfg.family == "encdec":
            enc_cfg = _dc.replace(cfg, causal=False)

            def enc_embed_fn(p, batch):
                return _embed_in(p, {"tokens": batch["enc_tokens"]},
                                 enc_cfg, settings)

            stages.append(_Stage("enc_embed", enc_embed_fn, "enc_embed"))
            for si, seg in enumerate(api.enc_segments):
                def enc_layer_fn(p_layer, x, seg=seg):
                    aux: Dict[str, Any] = {}
                    positions = (jnp.arange(x.shape[1])
                                 if enc_cfg.use_rope else None)
                    for i, bdef in enumerate(seg.blocks):
                        x, _ = apply_block(bdef, p_layer[f"b{i}"], x,
                                           enc_cfg, settings,
                                           positions=positions, aux=aux)
                    return x
                for rep in range(seg.n_repeat):
                    stages.append(_Stage(f"enc{si}_l{rep}", enc_layer_fn,
                                         "enc_layer"))

            def enc_final_fn(p, x):
                return rms_norm(x, p["enc_norm"]["scale"], cfg.norm_eps)

            stages.append(_Stage("enc_final", enc_final_fn, "enc_final"))
        elif cfg.family == "vlm":
            def vlm_enc_fn(p, batch):
                from repro.models.layers import dtype_of
                return batch["enc_embeddings"].astype(
                    dtype_of(settings.param_dtype))

            stages.append(_Stage("vlm_enc", vlm_enc_fn, "vlm_enc"))

        # ---- decoder stream
        stages.append(_Stage("embed",
                             lambda p, b: _embed_in(p, b, cfg, settings),
                             "embed"))

        has_enc = cfg.family in ("encdec", "vlm")
        for si, seg in enumerate(api.segments):
            takes_enc = has_enc and any(b.mixer == "cross"
                                        for b in seg.blocks)

            def layer_fn(p_layer, x, *rest, seg=seg):
                enc = rest[0] if rest else None
                aux: Dict[str, Any] = {}
                positions = (jnp.arange(x.shape[1]) if cfg.use_rope
                             else None)
                for i, bdef in enumerate(seg.blocks):
                    x, _ = apply_block(bdef, p_layer[f"b{i}"], x, cfg,
                                       settings, positions=positions,
                                       enc_kv=enc, aux=aux)
                return x
            for rep in range(seg.n_repeat):
                stages.append(_Stage(f"seg{si}_l{rep}", layer_fn,
                                     "layer", takes_enc=takes_enc))

        def head_fn(p, x, labels):
            # chunked under settings.ce_chunk, as in the whole-step loss
            nll, tokens = head_ce_terms(p, x, labels, cfg, settings)
            return nll / jnp.maximum(tokens, 1.0)
        stages.append(_Stage("head", head_fn, "head"))
        return stages

    def _stage_params(self, params) -> List[Any]:
        """Slice the model params into per-stage param trees (same order
        as self._stages)."""
        emb = {k: params[k] for k in ("embed", "pos_embed",
                                      "frontend_proj") if k in params}
        out: List[Any] = []
        for stage in self._stages:
            if stage.role in ("enc_embed", "embed"):
                out.append(emb)
            elif stage.role == "enc_final":
                out.append({"enc_norm": params["enc_norm"]})
            elif stage.role == "vlm_enc":
                out.append({})
            elif stage.role == "head":
                out.append({"final_norm": params["final_norm"],
                            "unembed": params["unembed"]})
            elif stage.role == "enc_layer":
                si, rep = self._seg_pos(stage.name)
                out.append(jax.tree.map(lambda a: a[rep],
                                        params["enc_segments"][si]))
            else:  # layer
                si, rep = self._seg_pos(stage.name)
                out.append(jax.tree.map(lambda a: a[rep],
                                        params["segments"][si]))
        return out

    @staticmethod
    def _seg_pos(name: str) -> Tuple[int, int]:
        """'seg0_l3' / 'enc1_l2' -> (segment index, repeat index)."""
        left, rep = name.split("_l")
        si = int("".join(ch for ch in left if ch.isdigit()) or 0)
        return si, int(rep)

    # ------------------------------------------------------------ step

    def _args_for(self, stage: _Stage, batch, x, xe, enc):
        if stage.role in ("enc_embed", "vlm_enc", "embed"):
            return (batch,)
        if stage.role in ("enc_layer", "enc_final"):
            return (xe,)
        if stage.role == "head":
            return (x, batch["labels"])
        if stage.takes_enc:
            return (x, enc)
        return (x,)

    def train_step(self, params, opt_state, batches: Sequence[Dict]) \
            -> Tuple[Any, Any, StepReport]:
        """One optimizer step over `batches` micro-batches."""
        t0 = time.perf_counter()
        self.tracker.reset_peak()
        stage_params = self._stage_params(params)
        n_stages = len(self._stages)
        grads = None
        loss_total = 0.0
        profiles = [ModuleProfile(s.name, 0, 0.0) for s in self._stages]
        bwd_begin_bytes = 0

        with obs.span("engine.step", cat="engine", step=self._step,
                      engine="staged"):
            for mb, batch in enumerate(batches):
                with self.spool.step(f"mb{mb}") as tx:
                    grads, loss_total, bwd_begin_bytes = \
                        self._run_microbatch(
                            tx, mb, batch, stage_params, n_stages, grads,
                            loss_total, profiles, bwd_begin_bytes)

            # ---------------- optimizer ----------------
            with obs.span("engine.update", cat="engine", step=self._step):
                params, opt_state, grad_norm = self._update(
                    self._unstage_grads(grads), opt_state, params,
                    1.0 / len(batches))
                jax.block_until_ready(jax.tree.leaves(params)[0])
        # The store tail is NOT synchronised here: adaptive offloading
        # (§3.3.3) schedules writes to complete inside the backward pass,
        # and any residue overlaps the next step's forward. Only the
        # profiling step drains the queue (to measure write bandwidth).
        profiling = self.policy.wants_profile and self._step == 0
        if profiling:
            self.spool.wait_io()
        step_time = time.perf_counter() - t0

        if profiling:
            self._profiles = profiles
            # Plan against the backend's measured per-tier bandwidths
            # (a tiered/striped store is not one scalar). The profiling
            # step's own writes raced jit compilation, so re-measure
            # with an uncontended burst sized like the largest module.
            max_bytes = max((p.bytes for p in profiles), default=0)
            self.spool.calibrate_backend(min(max_bytes, 8 << 20))
            cm = getattr(self.spool, "cache_manager", None)
            if cm is not None and \
                    hasattr(self.policy, "attach_cache_manager"):
                self.policy.attach_cache_manager(cm)
            self.policy.on_profile(profiles,
                                   self.spool.planner_bandwidth())
        self._step += 1
        return params, opt_state, StepReport(
            loss=loss_total / len(batches), step_time=step_time,
            peak_activation_bytes=self.tracker.peak,
            backward_begin_bytes=bwd_begin_bytes,
            stats=self.spool.stats, plan=self.plan,
            step=self._step, engine="staged",
            extra={"grad_norm": float(grad_norm)})

    def _run_microbatch(self, tx, mb, batch, stage_params, n_stages,
                        grads, loss_total, profiles, bwd_begin_bytes):
        """Forward + backward for one microbatch under step lease `tx`."""
        # ---------------- forward ----------------
        x = xe = enc = None
        kept: Dict[int, Any] = {}
        recompute_in: Dict[int, Any] = {}
        # offloaded stages' inputs as host numpy — the recompute
        # fallback's raw material if the blob is later unreadable
        fallback_in: Dict[int, Any] = {}
        loss = None
        fwd_sp = obs.span("engine.fwd", cat="engine", step=self._step,
                          mb=mb)
        fwd_sp.__enter__()
        for si, stage in enumerate(self._stages):
            # room for this stage's residuals and its working set, sized
            # by the largest stage profiled so far in this step
            self._make_device_room(2 * max(p.bytes for p in profiles))
            args = self._args_for(stage, batch, x, xe, enc)
            tin = time.perf_counter()
            if self.policy.recomputes(stage.role):
                out = stage.fn(stage_params[si], *args)
                recompute_in[si] = args
                self.tracker.alloc((tx.key(si), "k"), _nbytes(args),
                                   tag=f"ckpt:{tx.key(si)}")
                leaves = None
            else:
                out, leaves = stage.fwd(stage_params[si], *args)
                if self.policy.wants_profile and mb == 0:
                    # Profiling step: the first call of every stage
                    # paid jit compilation, which inflates the
                    # planner's deadline by orders of magnitude and
                    # makes it overcommit the store path. Release
                    # the cold call's buffers (so the footprint is
                    # not transiently doubled), then re-run warm and
                    # let `dt` below time that call.
                    jax.block_until_ready(out)
                    out = leaves = None
                    tin = time.perf_counter()
                    out, leaves = stage.fwd(stage_params[si], *args)
            if stage.role == "head":
                loss = out
            elif stage.role in ("enc_embed", "enc_layer"):
                xe = out
                jax.block_until_ready(xe)
            elif stage.role in ("enc_final", "vlm_enc"):
                enc = out
                jax.block_until_ready(enc)
            else:
                x = out
                jax.block_until_ready(x)
            dt = time.perf_counter() - tin

            if leaves is not None:
                p_leaves, r_leaves = stage.split_leaves(leaves)
                kept[si] = p_leaves      # params: never offloaded
                profile = ModuleProfile(
                    stage.name, _nbytes(list(r_leaves.values())), dt)
                if self.policy.should_offload(si, profile):
                    tx.offload(si, list(r_leaves.values()))
                    if self.on_fetch_fail == "recompute":
                        # host copies, off the device: the footprint the
                        # offload bought back is not spent again here
                        fallback_in[si] = jax.tree.map(np.asarray, args)
                else:
                    tx.keep(si, list(r_leaves.values()))
                profiles[si] = profile
                stage.cell.setdefault("resid_idx", tuple(r_leaves))
                # the store job now holds the only reference: a landed
                # store frees the device memory
                del r_leaves
            del leaves

        fwd_sp.__exit__(None, None, None)
        self.tracker.mark(f"backward_begin_{tx.step_id}")
        bwd_begin_bytes = max(bwd_begin_bytes, self.tracker.current)

        # ---------------- backward ----------------
        g = jnp.ones((), jnp.float32)   # d loss
        mb_grads: List[Any] = [None] * n_stages
        carry_g = g
        enc_grad = None
        bwd_sp = obs.span("engine.bwd", cat="engine", step=self._step,
                          mb=mb)
        bwd_sp.__enter__()
        for si in range(n_stages - 1, -1, -1):
            stage = self._stages[si]
            # one module ahead (§3.3.2) — including stage 0: the embed
            # stage's residuals were a cold blocking load under an old
            # `> 0` off-by-one. reuse_horizon is empty at si == 0.
            for s in reuse_horizon(range(si - 1, -1, -1)):
                tx.prefetch(s)
            if si in recompute_in:
                outs = stage.bwd_recompute(stage_params[si],
                                           recompute_in[si], carry_g)
                self.tracker.free((tx.key(si), "k"),
                                  tag=f"ckpt_done:{tx.key(si)}")
                recompute_in.pop(si)
            else:
                try:
                    r_list = tx.fetch(si)
                except (RuntimeError, OSError) as e:
                    # the blob is truly gone (retries exhausted, device
                    # dead): degrade to recomputing this stage's forward
                    # from the host copy of its input kept at offload
                    # time — the bottom rung of the ladder
                    if (self.on_fetch_fail != "recompute"
                            or si not in fallback_in):
                        raise
                    self.spool.stats.fetch_fallbacks += 1
                    if obs.is_enabled():
                        obs.instant("resilience.fetch_fallback",
                                    cat="resilience", stage=stage.name,
                                    key=tx.key(si), error=repr(e))
                    r_list = None
                if r_list is None:
                    args_dev = jax.tree.map(jnp.asarray,
                                            fallback_in.pop(si))
                    outs = stage.bwd_recompute(stage_params[si],
                                               args_dev, carry_g)
                    jax.block_until_ready(outs[0])
                else:
                    leaves = [None] * stage.cell["n_leaves"]
                    for i, l in kept[si].items():
                        leaves[i] = l
                    for i, l in zip(stage.cell["resid_idx"], r_list):
                        leaves[i] = l
                    outs = stage.bwd(tuple(leaves), carry_g)
                    jax.block_until_ready(outs[0])
                    # free this stage's residuals before the next fetch
                    # brings another stage's onto the device
                    del leaves, r_list
                tx.drop(si)
                kept.pop(si)
                fallback_in.pop(si, None)
            dp, dargs = outs[0], outs[1:]
            mb_grads[si] = dp
            # ---- cotangent routing
            if stage.role == "head":
                carry_g = dargs[0]
            elif stage.role == "layer":
                carry_g = dargs[0]
                if stage.takes_enc:
                    denc = dargs[1]
                    enc_grad = denc if enc_grad is None else \
                        jax.tree.map(jnp.add, enc_grad, denc)
            elif stage.role == "embed":
                # decoder stream exhausted; switch to encoder stream
                carry_g = enc_grad
            elif stage.role in ("enc_final", "enc_layer"):
                carry_g = dargs[0]
            # enc_embed / vlm_enc: chain ends
        bwd_sp.__exit__(None, None, None)
        loss_total += float(loss)
        if grads is None:
            grads = mb_grads
        else:
            grads = [jax.tree.map(jnp.add, a, b)
                     for a, b in zip(grads, mb_grads)]
        return grads, loss_total, bwd_begin_bytes

    def _unstage_grads(self, grads: List[Any]):
        """Reassemble per-stage grads into the model params structure
        (shared leaves — e.g. the embed table used by both encoder and
        decoder embed stages — accumulate by addition)."""
        out: Dict[str, Any] = {}

        def merge(d: Dict[str, Any]):
            for k, v in d.items():
                if k in out:
                    out[k] = jax.tree.map(jnp.add, out[k], v)
                else:
                    out[k] = v

        seg_reps: Dict[Tuple[str, int], List[Any]] = {}
        for stage, g in zip(self._stages, grads):
            if stage.role in ("enc_layer", "layer"):
                si, rep = self._seg_pos(stage.name)
                kind = "enc" if stage.role == "enc_layer" else "dec"
                seg_reps.setdefault((kind, si), []).append(g)
            elif stage.role != "vlm_enc":
                merge(g)

        dec_sis = sorted(s for k, s in seg_reps if k == "dec")
        if dec_sis:
            out["segments"] = [
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *seg_reps[("dec", si)]) for si in dec_sis]
        enc_sis = sorted(s for k, s in seg_reps if k == "enc")
        if enc_sis:
            out["enc_segments"] = [
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *seg_reps[("enc", si)]) for si in enc_sis]
        return out

    def close(self):
        """Idempotent: drain + join the spool, then remove any spool
        directories this trainer created (the seed leaked its
        `tba_spool_*` temp dirs)."""
        if self._closed:
            return
        self._closed = True
        self.spool.close()
        for d in self._owned_tmpdirs:
            shutil.rmtree(d, ignore_errors=True)
