"""Pipeline parallelism: GPipe fill–drain microbatch schedule expressed as
shard_map + lax.ppermute over a "pipe" mesh axis.

The forward schedule is written explicitly (stage s processes microbatch
m = t - s at tick t; activations hop stages through ppermute); the backward
schedule falls out of jax.grad — the transpose of ppermute is the reverse
ppermute, so AD derives the drain-order backward pipeline automatically.
This composes with the TBA activation spool at the driver level: the
per-microbatch residuals the schedule keeps alive are exactly the
activations the paper's §4.4 argument offloads.

1F1B note: with AD-generated backward the memory profile is GPipe's
(all M microbatch residuals live at the fill/drain boundary); 1F1B
interleaving is a memory optimization the TBA offload substitutes for —
offloading the fill-phase residuals achieves the same peak with a simpler
schedule (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, x_mb, mesh,
                   axis: str = "pipe"):
    """Run microbatches through a stage pipeline.

    stage_fn(stage_params, x) -> y        (same shape as x)
    params_stacked: pytree with leading dim = n_stages (sharded over axis)
    x_mb: (M, mb, ...) microbatched input
    Returns (M, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    M = x_mb.shape[0]
    T = M + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params_s, xs):
        # params_s: (1, ...) slice; xs: (M, mb, ...) only stage 0's real
        params_s = jax.tree.map(lambda a: a[0], params_s)
        sid = jax.lax.axis_index(axis)
        act_shape = xs.shape[1:]
        out = jnp.zeros((M,) + act_shape, xs.dtype)
        recv = jnp.zeros(act_shape, xs.dtype)

        def tick(carry, t):
            recv, out = carry
            m = t - sid                       # microbatch index here
            x_in = jnp.where(sid == 0, xs[jnp.clip(t, 0, M - 1)], recv)
            y = stage_fn(params_s, x_in)
            active = (m >= 0) & (m < M)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage collects its result; others forward it
            out = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(m, 0, M - 1), 0),
                lambda o: o, out)
            recv = jax.lax.ppermute(y, axis, perm) if perm else y
            return (recv, out), None

        (recv, out), _ = jax.lax.scan(tick, (recv, out), jnp.arange(T))
        return out[None]                      # (1, M, mb, ...) per stage

    specs_p = jax.tree.map(lambda _: P(axis), params_stacked)
    out = _shard_map(
        per_stage, mesh=mesh,
        in_specs=(specs_p, P()),              # x replicated; stage 0 reads
        out_specs=P(axis),
        check_vma=False,
    )(params_stacked, x_mb)
    return out[-1]                            # final stage's collection


def pipeline_loss_fn(stage_fn: Callable, loss_head: Callable, mesh,
                     axis: str = "pipe"):
    """Compose pipeline_apply with a loss head into a grad-able scalar fn:
    loss(params_stacked, x_mb, batch_aux) -> scalar."""

    def loss(params_stacked, x_mb, aux):
        y = pipeline_apply(stage_fn, params_stacked, x_mb, mesh, axis)
        return loss_head(y, aux)

    return loss
