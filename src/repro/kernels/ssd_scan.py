"""Mamba-2 SSD (state-space duality) chunked scan kernel (Pallas, TPU).

Grid (B, H, n_chunks); the chunk axis is the innermost sequential dimension
so the inter-chunk state recurrence lives in a VMEM scratch carry of shape
(P, N) f32 per (batch, head) program. Within a chunk the SSD decomposition
runs on the MXU:

    y_intra = (C B^T * exp(La_i - La_j) * causal) @ x          (Q x Q dots)
    y_inter = exp(La) * (C @ state^T)
    state'  = exp(La_last) * state + (x * exp(La_last - La))^T @ B

chunk=128 aligns the quadratic tile with the MXU. Validated against the
pure-jnp oracle (ref.ssd_reference / models.mamba2.ssd_chunked) in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract):
    """f32 contraction in full f32 on the MXU: the output is compared
    with the f32 sequential recurrence, and a one-pass bf16 contraction
    would round the operands to 8 bits first."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, lr_ref, lc_ref, b_ref, c_ref, y_ref, st_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    la_r = lr_ref[0, 0]                               # (1, Q) cumsum row
    la_c = lc_ref[0, 0]                               # (Q, 1) cumsum col
    b = b_ref[0].astype(jnp.float32)                  # (Q, N)
    c = c_ref[0].astype(jnp.float32)                  # (Q, N)

    # --- intra-chunk quadratic term ---
    g = _dot(c, b, ((1,), (1,)))                      # (Q, Q)
    dd = la_c - la_r                                  # (Q, Q) La_i - La_j
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(iq >= jq, jnp.exp(dd), 0.0)
    y = _dot(g * m, x, ((1,), (0,)))                  # (Q, P)

    # --- inter-chunk contribution from the carried state ---
    state = state_ref[...]                            # (P, N)
    y += jnp.exp(la_c) * _dot(c, state, ((1,), (1,)))  # (Q, P)

    # --- state update ---
    la_last = la_r[:, chunk - 1:]                     # (1, 1)
    w = jnp.exp(la_last - la_c) * x                   # (Q, P)
    s_new = _dot(w, b, ((0,), (0,)))                  # (P, N)
    state_ref[...] = state * jnp.exp(la_last) + s_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_c - 1)
    def _emit_state():
        st_ref[0, 0] = state_ref[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(xh, dA_log, B_s, C_s, *, chunk: int = 128,
                 interpret: bool = False):
    """xh: (B, S, H, P) inputs pre-scaled by dt; dA_log: (B, S, H);
    B_s, C_s: (B, S, N). Returns (y (B, S, H, P) f32, state (B,H,P,N) f32).
    S must be divisible by chunk (callers pad).

    The kernel sees heads as a leading dim, so every block's last two
    dims are (chunk, P), (chunk, N) or (P, N): the TPU tiling needs
    them aligned to (8, 128) or equal to the full array dims. The
    within-chunk cumsum of dA_log is taken here and handed in twice,
    as a (1, chunk) row and a (chunk, 1) column, so the kernel forms
    La_i - La_j by broadcasting instead of transposing a vector."""
    B, S, H, P = xh.shape
    N = B_s.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_c = S // chunk

    xt = xh.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    la = jnp.cumsum(dA_log.astype(jnp.float32).reshape(B, n_c, chunk, H),
                    axis=2).reshape(B, S, H).transpose(0, 2, 1)  # (B,H,S)
    la_row = la[:, :, None, :]                        # (B, H, 1, S)
    la_col = la[:, :, :, None]                        # (B, H, S, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    grid = (B, H, n_c)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, la_row, la_col, B_s, C_s)
    return y.transpose(0, 2, 1, 3), state
