"""RG-LRU gated linear recurrence kernel (Pallas, TPU target).

    h_t = exp(log_a_t) * h_{t-1} + x_t

Grid (B, W_blocks, n_chunks); the chunk axis is sequential with the hidden
state h (1, blk_w) f32 carried in VMEM scratch. Within a chunk the recurrence
runs as a fori_loop over time steps on the VPU — the recurrence is
elementwise over the width dim, so each step is a (blk_w,)-wide FMA; the
chunking exists to keep the working set in VMEM and to overlap the HBM
streams of log_a / x with compute. (A log-space prefix-scan variant trades
VPU steps for exp/cumsum passes but loses precision when log_a ~ -20 at
init; the sequential form is exact. The chunk loop, not the step loop, is
the HBM-bandwidth determinant.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, y_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows are read from the refs: Mosaic has no dynamic_slice of a
    # loaded value, but a dynamic pl.ds row of a ref is a plain load
    def step(t, h):                                   # h: (1, blk_w)
        row = pl.ds(t, 1)
        a = a_ref[0, row, :].astype(jnp.float32)
        x = x_ref[0, row, :].astype(jnp.float32)
        h = jnp.exp(a) * h + x
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("chunk", "blk_w", "interpret"))
def rglru_scan_fwd(log_a, x, *, chunk: int = 256, blk_w: int = 512,
                   interpret: bool = False):
    """log_a, x: (B, S, W) f32 -> h: (B, S, W) f32."""
    B, S, W = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    blk_w = min(blk_w, W)
    while W % blk_w:
        blk_w //= 2
    n_c = S // chunk
    n_w = W // blk_w

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    grid = (B, n_w, n_c)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, blk_w), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, blk_w), lambda b, w, c: (b, c, w)),
        ],
        out_specs=pl.BlockSpec((1, chunk, blk_w), lambda b, w, c: (b, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, blk_w), jnp.float32)],
        interpret=interpret,
    )(log_a, x)
    return y
