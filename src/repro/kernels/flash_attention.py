"""Fused GQA flash attention forward kernel (Pallas, TPU target).

TPU adaptation of FlashAttention-2 (the paper's evaluation substrate, §4.1):
online-softmax tiling with the KV axis as the innermost sequential grid
dimension, carry (m, l, acc) in VMEM scratch, and MXU-aligned (128, 128)
score tiles. GQA is expressed in the index maps: query-head program b reads
kv head b // group_size, so KV tiles are fetched once per group from HBM.

Supports: causal masking, sliding window, logit softcap (gemma2), any
Hq % Hkv == 0. Validated against models.attention.attend_chunked (the pure
jnp oracle in kernels/ref.py) in interpret mode on CPU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, logit_cap: float,
                 blk_q: int, blk_k: int, n_kv: int, kv_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (blk_q, D)
    k = k_ref[0].astype(jnp.float32)                  # (blk_k, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if logit_cap:
        s = jnp.tanh(s / logit_cap) * logit_cap

    rows = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 0)
    cols = kj * blk_k + jax.lax.broadcasted_iota(jnp.int32,
                                                 (blk_q, blk_k), 1)
    mask = cols < kv_len                  # padded keys are invalid
    if causal:
        mask &= cols <= rows
    if window:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "logit_cap", "blk_q",
                              "blk_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        logit_cap: float = 0.0,
                        blk_q: int = DEFAULT_BLOCK_Q,
                        blk_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    # (B*H, S, D) layout; pad sequence to block multiples
    qt = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    blk_q = min(blk_q, max(Sq, 8))
    blk_k = min(blk_k, max(Skv, 8))
    qt, sq0 = _pad_to(qt, 1, blk_q)
    kt, sk0 = _pad_to(kt, 1, blk_k)
    vt, _ = _pad_to(vt, 1, blk_k)
    n_q = qt.shape[1] // blk_q
    n_kv = kt.shape[1] // blk_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, blk_q=blk_q, blk_k=blk_k, n_kv=n_kv,
        kv_len=sk0)

    grid = (B * Hq, n_q, n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D),
                         lambda b, i, j, G=G, Hq=Hq, Hkv=Hkv:
                         ((b // Hq) * Hkv + (b % Hq) // G, j, 0)),
            pl.BlockSpec((1, blk_k, D),
                         lambda b, i, j, G=G, Hq=Hq, Hkv=Hkv:
                         ((b // Hq) * Hkv + (b % Hq) // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :sq0].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    return out
