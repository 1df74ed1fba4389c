"""Optimizers (functional, optax-style but dependency-free).

SGD matches the paper's evaluation choice (§4.1: "we use SGD instead of Adam
as the optimizer to reduce the memory use by optimizer states"); AdamW is the
production default. ZeRO-1 sharding of the optimizer state is expressed as a
PartitionSpec tree (zero1_shardings) consumed by the launcher.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any        # first moment (AdamW) or momentum (SGD); None-tree if off
    nu: Any        # second moment (AdamW only)


@dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], OptState]
    update: Callable[[Any, OptState, Any], Tuple[Any, OptState]]
    name: str = "opt"
    #: global-norm clip threshold the fused `update` applies (None = off).
    #: Exposed so schedulers can tell whether the update needs all grads
    #: at once — per-layer eager updates are only valid when this is None.
    clip_norm: Optional[float] = None
    #: per-leaf kernel `(p, m, v, g, step) -> (new_p, new_m, new_v)` with
    #: math identical to the fused `update` (step is the post-increment
    #: step index, i.e. `state.step + 1`). `m`/`v` are None for
    #: optimizers without that moment. Drives the eager overlapped path.
    leaf_update: Optional[Callable] = None


def global_norm(tree) -> jnp.ndarray:
    """f32 L2 norm over every leaf of `tree`."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale)
                        .astype(g.dtype), grads), gn


def sgd(lr: float = 1e-3, momentum: float = 0.0,
        clip_norm: Optional[float] = None) -> Optimizer:
    def init(params):
        mu = (jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
              if momentum else None)
        return OptState(jnp.zeros((), jnp.int32), mu, None)

    def update(grads, state, params):
        if clip_norm:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        if momentum:
            mu = jax.tree.map(
                lambda m, g: momentum * m + g.astype(jnp.float32),
                state.mu, grads)
            upd = mu
        else:
            mu, upd = None, grads
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32)
                          - lr * u.astype(jnp.float32)).astype(p.dtype),
            params, upd)
        return new_params, OptState(state.step + 1, mu, None)

    def leaf_update(p, m, v, g, step):
        del v, step
        if momentum:
            mu = momentum * m + g.astype(jnp.float32)
            u = mu
        else:
            mu, u = None, g
        new_p = (p.astype(jnp.float32)
                 - lr * u.astype(jnp.float32)).astype(p.dtype)
        return new_p, mu, None

    return Optimizer(init, update, "sgd", clip_norm=clip_norm,
                     leaf_update=leaf_update)


def adamw(lr: float = 3e-4, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1,
          clip_norm: Optional[float] = 1.0,
          warmup_steps: int = 0) -> Optimizer:
    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return OptState(jnp.zeros((), jnp.int32),
                        jax.tree.map(z, params), jax.tree.map(z, params))

    def update(grads, state, params):
        if clip_norm:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        step = state.step + 1
        sched = jnp.minimum(1.0, step / max(warmup_steps, 1)) \
            if warmup_steps else 1.0
        lr_t = lr * sched
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1)
                          * g.astype(jnp.float32), state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2)
                          * jnp.square(g.astype(jnp.float32)),
                          state.nu, grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m, v):
            mhat = m / bc1
            vhat = v / bc2
            u = mhat / (jnp.sqrt(vhat) + eps)
            u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * u).astype(p.dtype)

        new_params = jax.tree.map(upd, params, mu, nu)
        return new_params, OptState(step, mu, nu)

    def leaf_update(p, m, v, g, step):
        sched = jnp.minimum(1.0, step / max(warmup_steps, 1)) \
            if warmup_steps else 1.0
        lr_t = lr * sched
        mu = b1 * m + (1 - b1) * g.astype(jnp.float32)
        nu = b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32))
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)
        mhat = mu / bc1
        vhat = nu / bc2
        u = mhat / (jnp.sqrt(vhat) + eps)
        u = u + weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr_t * u).astype(p.dtype)
        return new_p, mu, nu

    return Optimizer(init, update, "adamw", clip_norm=clip_norm,
                     leaf_update=leaf_update)


def zero1_shardings(params_specs, dp_axes: Tuple[str, ...]):
    """ZeRO-1: shard optimizer moments over the data axes on each leaf's
    largest unsharded dimension (falls back to the param's own spec)."""
    def shard_one(spec: P):
        parts = list(spec) if spec else []
        if not parts:
            return P(dp_axes)  # shard dim0 of an otherwise replicated leaf
        for i, p_ in enumerate(parts):
            if p_ is None:
                parts[i] = dp_axes
                return P(*parts)
        return P(*parts)

    return jax.tree.map(shard_one, params_specs,
                        is_leaf=lambda x: isinstance(x, P))
