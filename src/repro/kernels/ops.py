"""Jitted public wrappers for the Pallas kernels.

`causal_attention` is the training path's attention on TPU: the splash
kernels that ship with JAX (forward, dq, dk/dv), whose custom_vjp keeps
(q, k, v, out, lse) and rebuilds each tile in VMEM, skipping fully masked
causal blocks. The other wrappers run a Pallas forward and differentiate
a pure-JAX formulation in backward: `flash_attention` the blockwise
chunked path (no (Sq, Skv) scores), the scans their oracles.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd


# ------------------------------------------------------------ attention

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, logit_cap, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               logit_cap=logit_cap, interpret=interpret)


def _fa_fwd(q, k, v, causal, window, logit_cap, interpret):
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              logit_cap=logit_cap, interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, logit_cap, interpret, res, g):
    from repro.models.attention import attend_chunked
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: attend_chunked(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            chunk=128),
        q, k, v)
    return vjp(g)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, interpret: bool = False):
    return _flash_attention(q, k, v, causal, window, logit_cap, interpret)


@functools.lru_cache(maxsize=32)
def _splash(seq: int, heads: int, block: int, interpret: bool):
    """The splash kernel for causal attention over `heads` query heads
    (any number of kv heads that divides them). Its mask tables are
    built eagerly, so a cached kernel holds no tracer of the trace that
    first asked for it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    b = block
    sizes = sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                          block_q_dkv=b, block_kv_dkv=b,
                          block_kv_dkv_compute=b, block_q_dq=b,
                          block_kv_dq=b)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, *, block: int, interpret: bool = False):
    """Causal self-attention through the fused splash pair.

    q: (B, S, Hq, D); k, v: (B, S, Hkv, D); S % block == 0. Returns
    (B, S, Hq, D) in q.dtype. The MXU takes q, k, v in their own dtype
    with f32 accumulation; softmax statistics stay f32. q is scaled by
    D**-0.5 in f32 and rounded once to its dtype.
    """
    B, S, Hq, D = q.shape
    kernel = _splash(S, Hq, block, interpret)
    qs = (q.astype(jnp.float32) * D ** -0.5).astype(q.dtype)
    out = jax.vmap(kernel)(qs.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2))
    return out.swapaxes(1, 2)


# ------------------------------------------------------------ SSD scan

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd_scan(xh, dA_log, B_s, C_s, chunk, interpret):
    return ssd_scan_fwd(xh, dA_log, B_s, C_s, chunk=chunk,
                        interpret=interpret)


def _ssd_fwd(xh, dA_log, B_s, C_s, chunk, interpret):
    out = ssd_scan_fwd(xh, dA_log, B_s, C_s, chunk=chunk,
                       interpret=interpret)
    return out, (xh, dA_log, B_s, C_s)


def _ssd_bwd(chunk, interpret, res, g):
    xh, dA_log, B_s, C_s = res
    _, vjp = jax.vjp(
        lambda *a: ref.ssd_reference(*a), xh, dA_log, B_s, C_s)
    return vjp(g)


_ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(xh, dA_log, B_s, C_s, *, chunk: int = 128,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return _ssd_scan(xh, dA_log, B_s, C_s, chunk, interpret)


# ------------------------------------------------------------ RG-LRU scan

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rglru_scan(log_a, x, interpret):
    return rglru_scan_fwd(log_a, x, interpret=interpret)


def _rg_fwd(log_a, x, interpret):
    return rglru_scan_fwd(log_a, x, interpret=interpret), (log_a, x)


def _rg_bwd(interpret, res, g):
    log_a, x = res
    _, vjp = jax.vjp(lambda a, b: ref.rglru_reference(a, b), log_a, x)
    return vjp(g)


_rglru_scan.defvjp(_rg_fwd, _rg_bwd)


def rglru_scan(log_a, x, *, interpret: bool = False):
    return _rglru_scan(log_a, x, interpret)
