"""Jitted step builders shared by the dry-run, the trainer and the server.

Each builder returns (step_fn, example_args) where example_args is a tree of
ShapeDtypeStructs with NamedShardings attached — `jax.jit(step_fn).lower(
*example_args)` is everything the dry-run needs, and the trainer feeds real
arrays with the same shardings.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.api import ModelApi
from repro.models.transformer import RunSettings
from repro.optim.optimizers import Optimizer, global_norm
from repro.parallel.sharding import (MeshAxes, batch_specs, cache_specs,
                                     param_specs, with_sharding)

# Serving keeps weights TP-only (no per-step all-gather) while they fit;
# above this per-chip budget the dry-run falls back to fsdp sharding.
SERVE_TP_ONLY_BUDGET = 8 << 30


def count_params(params_shapes, *, exclude=("embed", "pos_embed")) -> int:
    """Number of parameters, excluding lookup-only tables (for 6ND)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        names = [getattr(k, "key", None) for k in path]
        if any(n in exclude for n in names):
            continue
        total += leaf.size
    return total


def active_param_count(cfg: ModelConfig, params_shapes) -> int:
    """Active params per token: for MoE, only top_k of the expert stacks
    (plus shared experts / router / attention) touch a given token."""
    n = count_params(params_shapes)
    if not cfg.moe_num_experts:
        return n
    moe = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shapes)[0]:
        names = [getattr(k, "key", None) for k in path]
        if "moe" in names and any(s in names
                                  for s in ("w_in", "w_gate", "w_out")):
            moe += leaf.size
    return n - moe + (moe * cfg.moe_top_k) // cfg.moe_num_experts


def param_bytes(params_shapes) -> int:
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree.leaves(params_shapes))


def build_settings(cfg: ModelConfig, mesh, axes: MeshAxes, *, kind: str,
                   activation_policy: Optional[str] = None,
                   attn_chunk: int = 1024,
                   ce_chunk: int = 512) -> RunSettings:
    policy = activation_policy or ("offload" if kind == "train" else "keep")
    is_moe = cfg.moe_num_experts > 0
    return RunSettings(
        attn_impl="xla", attn_chunk=attn_chunk,
        activation_policy=policy, offload_names=("blk_in",),
        mesh=mesh,
        ep_axis="model" if is_moe else None,
        tp_axis=axes.tp,
        dp_axes=axes.dp,
        param_dtype=cfg.dtype,
        ce_chunk=ce_chunk if kind == "train" else 0)


def make_host_train_step(api: ModelApi, optimizer: Optimizer,
                         settings: RunSettings, *, mesh=None,
                         axes: Optional[MeshAxes] = None,
                         donate_opt_state: bool = True) -> Callable:
    """Whole-step jitted train step for the single-host jit engine —
    shared by `repro.session.TrainSession` and `repro.launch.train`.
    Signature matches what TrainLoop drives:
    (params, opt_state, batch) -> (params, opt_state, metrics).

    With the "spool" activation policy (per-layer offloading via
    repro.core.hooks), the optimizer's step counter is threaded into the
    batch under the reserved "_spool_step" key — the traced scalar the
    hooks key their spool step-leases on.

    The metrics carry the step's `grad_norm` (before any clipping), a
    number that depends on the backward, unlike the loss.

    With a `mesh`, each numpy batch from the loader is placed with
    dp-sharded batch specs before entering the jitted step, so the
    program partitions across the mesh (params/opt state placement is
    the caller's job — `TrainSession.init` device_puts them); the spool
    hooks then run their callbacks per shard under a shard_map.

    The step donates `params` and `opt_state`: the new state is written
    over the old, so the caller must not reuse what it passed in.
    `donate_opt_state=False` keeps the optimizer state undonated, for
    callers whose opt state may still be read by a spool store in
    flight (TrainLoop's "opt_state" host offload forwards it)."""
    hooked = (settings.activation_policy == "spool"
              and settings.hook_bridge is not None)

    @functools.partial(jax.jit,
                       donate_argnums=(0, 1) if donate_opt_state else (0,))
    def step_fn(params, opt_state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if hooked:
            batch["_spool_step"] = opt_state.step
        (_, metrics), grads = jax.value_and_grad(
            api.loss, has_aux=True)(params, batch, settings)
        metrics = dict(metrics, grad_norm=global_norm(grads))
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, metrics

    if mesh is None:
        return step_fn
    axes = axes or MeshAxes()

    def sharded_step(params, opt_state, batch):
        arrs = {k: np.asarray(v) for k, v in batch.items()}
        specs = batch_specs(arrs, mesh, axes)
        batch = jax.device_put(
            arrs, {k: NamedSharding(mesh, specs[k]) for k in arrs})
        return step_fn(params, opt_state, batch)

    return sharded_step


def make_overlap_train_step(api: ModelApi, optimizer: Optimizer,
                            settings: RunSettings, opt_bridge, *,
                            mesh=None,
                            axes: Optional[MeshAxes] = None) -> Callable:
    """Eager-overlap variant of `make_host_train_step`.

    The jitted program computes only (metrics, grads): the per-layer
    grad taps (`settings.opt_sink`, see repro.core.hooks) stream each
    scanned layer's gradients to the OptBridge as backward produces
    them, and the bridge's side stream fetches/updates/stages that
    layer's opt-state moments while XLA is still in the next layer's
    backward. The Python wrapper keeps the TrainLoop contract
    ``(params, opt_state, batch) -> (params, opt_state, metrics)``:
    it joins the side stream only after blocking on the grads (by then
    every tap has fired — the taps' tokens are data dependencies of the
    grads) and applies the non-scanned rest of the tree on the main
    thread with the same kernels. `opt_state` is the bridge's light
    ``(step, None, None)`` husk after the first step; the incoming full
    state seeds the bridge lazily (init and resume both land here)."""
    axes = axes or MeshAxes()

    @jax.jit
    def grad_fn(params, step, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        batch["_spool_step"] = step
        (_, metrics), grads = jax.value_and_grad(
            api.loss, has_aux=True)(params, batch, settings)
        return metrics, grads

    def step_fn(params, opt_state, batch):
        opt_bridge.ensure_seeded(opt_state, params)
        step_i = int(opt_state.step)
        opt_bridge.begin_step(params, step_i)
        if mesh is not None:
            arrs = {k: np.asarray(v) for k, v in batch.items()}
            specs = batch_specs(arrs, mesh, axes)
            batch = jax.device_put(
                arrs, {k: NamedSharding(mesh, specs[k]) for k in arrs})
        metrics, grads = grad_fn(params,
                                 jnp.asarray(step_i, jnp.int32), batch)
        jax.block_until_ready(grads)
        new_params, new_opt = opt_bridge.finish_step(params, grads)
        return new_params, new_opt, metrics

    return step_fn


@dataclass
class StepBundle:
    fn: Callable                  # jit-able step function
    args: Tuple[Any, ...]         # ShapeDtypeStructs with shardings
    out_shardings: Any            # or None (auto)
    settings: RunSettings
    param_specs: Any
    n_params: int                 # for 6ND (excludes lookup tables)
    n_active: int
    tokens_per_step: int
    fsdp: bool


def _params_sds(api: ModelApi):
    return jax.eval_shape(api.init, jax.random.key(0))


def make_train_step(api: ModelApi, mesh, axes: MeshAxes,
                    optimizer: Optimizer, shape: ShapeConfig,
                    *, activation_policy: Optional[str] = None,
                    ce_chunk: int = 512,
                    settings: Optional[RunSettings] = None) -> StepBundle:
    cfg = api.cfg
    settings = settings or build_settings(
        cfg, mesh, axes, kind="train", activation_policy=activation_policy,
        ce_chunk=ce_chunk)

    p_sds = _params_sds(api)
    p_specs = param_specs(cfg, p_sds, mesh, axes, fsdp=True)
    params = with_sharding(p_sds, p_specs, mesh)
    o_sds = jax.eval_shape(optimizer.init, p_sds)
    # moments inherit the param specs (ZeRO: fully sharded optimizer state)
    o_specs = type(o_sds)(
        step=P(),
        mu=None if o_sds.mu is None else p_specs,
        nu=None if o_sds.nu is None else p_specs)
    opt_state = with_sharding(o_sds, o_specs, mesh)
    b_sds = api.input_specs(shape)["batch"]
    b_specs = batch_specs(b_sds, mesh, axes)
    batch = with_sharding(b_sds, b_specs, mesh)

    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                        is_leaf=lambda x: isinstance(x, P))
    o_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                        is_leaf=lambda x: isinstance(x, P))

    # NOTE: output layouts are pinned with with_sharding_constraint instead
    # of jit(out_shardings=...): explicit out_shardings on a module that
    # contains memory-space annotations (the pinned_host activation
    # offload) trips XLA's SPMD partitioner ("side-effect ops cannot be
    # replicated" on annotate_device_placement custom-calls).
    def train_step(params, opt_state, batch):
        if settings.activation_policy == "spool" \
                and settings.hook_bridge is not None:
            # per-layer spool hooks; on a multi-device mesh the hooks
            # wrap their io_callbacks in a shard_map (GSPMD cannot
            # partition a bare io_callback), so every device streams
            # its local residual shard — see repro.core.hooks
            batch = dict(batch)
            batch["_spool_step"] = opt_state.step
        (_, metrics), grads = jax.value_and_grad(
            api.loss, has_aux=True)(params, batch, settings)
        params, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.lax.with_sharding_constraint(params, p_sh)
        opt_state = jax.lax.with_sharding_constraint(opt_state, o_sh)
        return params, opt_state, metrics

    return StepBundle(
        fn=train_step, args=(params, opt_state, batch),
        out_shardings=None,
        settings=settings, param_specs=p_specs,
        n_params=count_params(p_sds),
        n_active=active_param_count(cfg, p_sds),
        tokens_per_step=shape.global_batch * shape.seq_len, fsdp=True)


def _serve_fsdp(mesh, axes: MeshAxes, p_sds) -> bool:
    per_chip = param_bytes(p_sds) // axes.tp_size(mesh)
    return per_chip > SERVE_TP_ONLY_BUDGET


def make_prefill_step(api: ModelApi, mesh, axes: MeshAxes,
                      shape: ShapeConfig,
                      *, settings: Optional[RunSettings] = None) \
        -> StepBundle:
    cfg = api.cfg
    settings = settings or build_settings(cfg, mesh, axes, kind="prefill")
    emit_cache = cfg.has_decode

    def prefill_step(params, batch):
        if emit_cache:
            return api.prefill(params, batch, settings,
                               cache_len=shape.seq_len)
        logits, _ = api.forward(params, batch, settings)
        return logits

    p_sds = _params_sds(api)
    fsdp = _serve_fsdp(mesh, axes, p_sds)
    p_specs = param_specs(cfg, p_sds, mesh, axes, fsdp=fsdp)
    params = with_sharding(p_sds, p_specs, mesh)
    b_sds = api.input_specs(shape, for_loss=False)["batch"]
    batch = with_sharding(b_sds, batch_specs(b_sds, mesh, axes), mesh)
    return StepBundle(
        fn=prefill_step, args=(params, batch), out_shardings=None,
        settings=settings, param_specs=p_specs,
        n_params=count_params(p_sds),
        n_active=active_param_count(cfg, p_sds),
        tokens_per_step=shape.global_batch * shape.seq_len, fsdp=fsdp)


def make_decode_step(api: ModelApi, mesh, axes: MeshAxes,
                     shape: ShapeConfig,
                     *, settings: Optional[RunSettings] = None) \
        -> StepBundle:
    cfg = api.cfg
    settings = settings or build_settings(cfg, mesh, axes, kind="decode")

    def decode_step(params, cache, batch, pos):
        return api.decode_step(params, cache, batch, pos, settings)

    p_sds = _params_sds(api)
    fsdp = _serve_fsdp(mesh, axes, p_sds)
    p_specs = param_specs(cfg, p_sds, mesh, axes, fsdp=fsdp)
    params = with_sharding(p_sds, p_specs, mesh)
    specs = api.input_specs(shape)
    b_sds, c_sds = specs["batch"], specs["cache"]
    batch = with_sharding(b_sds, batch_specs(b_sds, mesh, axes), mesh)
    cache = with_sharding(c_sds, cache_specs(c_sds, mesh, axes), mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    return StepBundle(
        fn=decode_step, args=(params, cache, batch, pos),
        out_shardings=None, settings=settings, param_specs=p_specs,
        n_params=count_params(p_sds),
        n_active=active_param_count(cfg, p_sds),
        tokens_per_step=shape.global_batch, fsdp=fsdp)


def make_step(api: ModelApi, mesh, axes: MeshAxes, shape: ShapeConfig,
              optimizer: Optional[Optimizer] = None, **kw) -> StepBundle:
    if shape.kind == "train":
        assert optimizer is not None
        return make_train_step(api, mesh, axes, optimizer, shape, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(api, mesh, axes, shape, **kw)
    return make_decode_step(api, mesh, axes, shape, **kw)
