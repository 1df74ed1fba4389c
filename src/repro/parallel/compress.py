"""Int8 error-feedback gradient compression for the DP all-reduce
(beyond-paper distributed-optimization feature; off by default).

Scheme (1-bit-Adam-family, simplified to int8):
  1. e += g                      (fold in the error-feedback residual)
  2. q = round(e / scale), scale = max|e| / 127     (per-leaf)
  3. e  = e - q * scale          (new residual: what quantization lost)
  4. all-gather (q, scale) over the dp axis, dequantize, mean

Wire cost per device: N bytes * (dp-1)/dp (int8 gather) + dp scales,
vs 2 * 2N * (dp-1)/dp for a bf16 ring all-reduce — a ~4x reduction.
Error feedback keeps the *accumulated* quantization error bounded, so SGD
converges to the same neighborhood (verified by tests/test_compress.py).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P


def init_error_state(grads_like) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32),
                        grads_like)


def _quantize(e):
    scale = jnp.max(jnp.abs(e)) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.clip(jnp.round(e / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _leaf_compressed_mean(g, e, axis: str):
    """Inside shard_map: per-device grad g -> mean over `axis` via int8."""
    e = e + g.astype(jnp.float32)
    q, scale = _quantize(e)
    e_new = e - q.astype(jnp.float32) * scale
    qs = jax.lax.all_gather(q, axis)                 # (n, ...)
    ss = jax.lax.all_gather(scale, axis)             # (n,)
    n = qs.shape[0]
    deq = (qs.astype(jnp.float32)
           * ss.reshape((n,) + (1,) * (qs.ndim - 1)))
    return deq.mean(axis=0).astype(g.dtype), e_new


def compressed_mean_grads(grads, err_state, mesh, axis: str):
    """Mean per-device grads over the dp `axis` with int8 error feedback.

    grads/err_state: pytrees of per-device (unreduced) gradients living
    replicated over the other axes. Returns (mean_grads, new_err_state).
    """

    def body(g_tree, e_tree):
        pairs = jax.tree.map(
            lambda g, e: _leaf_compressed_mean(g, e, axis), g_tree, e_tree)
        means = jax.tree.map(lambda p: p[0], pairs,
                             is_leaf=lambda x: isinstance(x, tuple))
        errs = jax.tree.map(lambda p: p[1], pairs,
                            is_leaf=lambda x: isinstance(x, tuple))
        return means, errs

    spec = jax.tree.map(lambda _: P(), grads)
    return _shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False,
    )(grads, err_state)


def exact_mean_grads(grads, mesh, axis: str):
    """Reference bf16/f32 psum-mean (what compression replaces)."""

    def body(g_tree):
        return jax.tree.map(
            lambda g: (jax.lax.psum(g.astype(jnp.float32), axis)
                       / mesh.shape[axis]).astype(g.dtype), g_tree)

    spec = jax.tree.map(lambda _: P(), grads)
    return _shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                      check_vma=False)(grads)
