"""GQA attention: memory-efficient chunked online-softmax (XLA path),
decode-step attention against full or ring KV caches, and dispatch to the
fused Pallas pair for causal self-attention on TPU (`fused_block`).

The chunked XLA path is mathematically identical to the Pallas kernels
and serves as their oracle, and as the path on other backends and shapes;
it never materialises an (Sq, Skv) score tensor larger than (Sq, chunk),
which is what makes the 32k/500k cells lowerable. Differentiated by JAX,
though, its scan stacks every tile's f32 scores for backward; the fused
pair keeps only (q, k, v, out, lse) and rebuilds the tiles in VMEM.

Layout notes (measured on the 256-chip dry-run): KV heads are expanded to
the query head count *inside* each chunk iteration, so every score/carry
tensor keeps a clean (batch@dp, heads@tp) layout — reshaping q to
(B, S, Hkv, G, D) instead makes GSPMD split heads across two tiny dims and
replicate the batch (48 GB/device of f32 carries on qwen train_4k). The
expansion is a broadcast of already-replicated KV, fused into the einsum.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import hint, softcap

NEG_INF = -1.0e30


def _pick_chunk(skv: int, requested: int) -> int:
    if skv <= requested:
        return skv
    c = requested
    while skv % c:
        c //= 2
    return max(c, 1)


def _expand_kv(blk, G: int):
    """(B, C, Hkv, D) -> (B, C, Hkv*G, D) by repeating each kv head G x."""
    if G == 1:
        return blk
    B, C, Hkv, D = blk.shape
    blk = jnp.broadcast_to(blk[:, :, :, None, :], (B, C, Hkv, G, D))
    return blk.reshape(B, C, Hkv * G, D)


MAX_Q_BLOCKS = 8


def attend_blocked(q, k, v, *, causal: bool, window: int = 0,
                   logit_cap: float = 0.0, chunk: int = 1024,
                   settings: Any = None, n_blocks: int = MAX_Q_BLOCKS):
    """Causal/windowed attention with *static triangular KV extents*.

    The plain chunked path computes every (q, kv) tile and masks — half
    the MXU work of a causal layer is thrown away (and for sliding-window
    layers at long context, almost all of it). Splitting queries into
    unrolled blocks gives each block a statically-sliced KV range:

        causal:  kv in [0, (i+1)*qblk)                (~(n+1)/2n of full)
        window:  kv in [floor_to_chunk(lo), hi)       (~(w+qblk)/S of full)

    This is the flash-kernel block-skipping trick expressed at the XLA
    graph level, so the dry-run roofline (and a real TPU run of the XLA
    path) sees the reduced FLOPs. Unroll factor is capped so the HLO
    stays small (inner online-softmax scans are shared per extent).
    """
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    n_blocks = min(n_blocks, Sq)
    while Sq % n_blocks:
        n_blocks -= 1
    qblk = Sq // n_blocks
    outs = []
    for i in range(n_blocks):
        lo_q = i * qblk
        hi_kv = min((i + 1) * qblk, Skv) if causal else Skv
        lo_kv = 0
        if window:
            lo_kv = max(0, lo_q - window + 1)
            lo_kv = (lo_kv // chunk) * chunk        # chunk-aligned
        qi = jax.lax.slice_in_dim(q, lo_q, lo_q + qblk, axis=1)
        ki = jax.lax.slice_in_dim(k, lo_kv, hi_kv, axis=1)
        vi = jax.lax.slice_in_dim(v, lo_kv, hi_kv, axis=1)
        outs.append(attend_chunked(
            qi, ki, vi, causal=causal, window=window,
            logit_cap=logit_cap, q_offset=lo_q - lo_kv, chunk=chunk,
            settings=settings))
    return jnp.concatenate(outs, axis=1)


def attend_chunked(q, k, v, *, causal: bool, window: int = 0,
                   logit_cap: float = 0.0, q_offset=0,
                   kv_len: Optional[jnp.ndarray] = None,
                   chunk: int = 1024, settings: Any = None):
    """Online-softmax attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); Hq % Hkv == 0.
    window: 0 = unbounded; >0 = keys within [i - window + 1, i].
    q_offset: absolute position of q[0] (decode/prefill continuation).
    kv_len: optional scalar/array — keys at index >= kv_len are invalid.
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5)
    qf = hint(qf, settings, "b", None, "h", None)

    C = _pick_chunk(Skv, chunk)
    n_chunks = Skv // C
    kc = k.reshape(B, n_chunks, C, Hkv, D)
    vc = v.reshape(B, n_chunks, C, Hkv, D)

    iq = (jnp.arange(Sq) + q_offset)[:, None]            # (Sq, 1)

    def body(carry, inputs):
        m, l, acc = carry
        c_idx, k_blk, v_blk = inputs                     # (B, C, Hkv, D)
        k_blk = _expand_kv(k_blk.astype(jnp.float32), G)
        v_blk = _expand_kv(v_blk.astype(jnp.float32), G)
        s = jnp.einsum("bqhd,bchd->bqhc", qf, k_blk)     # (B,Sq,Hq,C)
        if logit_cap:
            s = softcap(s, logit_cap)
        jc = c_idx * C + jnp.arange(C)[None, :]          # (1, C)
        mask = jnp.ones((Sq, C), bool)
        if causal:
            mask &= jc <= iq
        if window:
            mask &= jc > iq - window
        if kv_len is not None:
            mask &= jc < kv_len
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bqhc,bchd->bqhd", p, v_blk)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = hint(jnp.full((B, Sq, Hq), NEG_INF, jnp.float32), settings,
              "b", None, "h")
    l0 = hint(jnp.zeros((B, Sq, Hq), jnp.float32), settings,
              "b", None, "h")
    a0 = hint(jnp.zeros((B, Sq, Hq, D), jnp.float32), settings,
              "b", None, "h", None)
    if n_chunks == 1:
        (m, l, acc), _ = body((m0, l0, a0),
                              (jnp.array(0), kc[:, 0], vc[:, 0]))
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.arange(n_chunks), kc.swapaxes(0, 1), vc.swapaxes(0, 1)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def attend_decode(q, cache_k, cache_v, pos, *, window: int = 0,
                  logit_cap: float = 0.0, ring: bool = False,
                  settings: Any = None):
    """One-step decode attention. q: (B, 1, Hq, D); cache: (B, S, Hkv, D).

    pos: absolute position of the current token (already written into
    the cache by the caller) — a scalar int32, or a (B,) int32 vector
    when every batch row sits at its own position (continuous batching:
    each serving slot decodes a different sequence). With ring=True the
    cache length S equals the window and slot s holds absolute position
    `s + S*floor((pos - s)/S)` (i.e. the most recent token congruent to s).
    """
    B, _, Hq, D = q.shape
    S, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32) * (D ** -0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, cache_k.astype(jnp.float32))
    if logit_cap:
        s = softcap(s, logit_cap)
    slots = jnp.arange(S)
    pos = jnp.asarray(pos)
    # per-row positions mask as (B, S); a scalar keeps the shared (S,)
    # mask (broadcast over batch) — same values either way
    posk = pos[:, None] if pos.ndim == 1 else pos
    if ring:
        slot_pos = slots + S * ((posk - slots) // S)     # absolute positions
        valid = (slot_pos >= 0) & (slot_pos <= posk)
        if window:
            valid &= slot_pos > posk - window
    else:
        valid = slots <= posk
        if window:
            valid &= slots > posk - window
    if valid.ndim == 1:
        valid = valid[None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, cache_v.astype(jnp.float32))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


FUSED_BLOCKS = (512, 256, 128)


def fused_block(platform: str, *, sq: int, skv: int, head_dim: int,
                causal: bool, window: int = 0, logit_cap: float = 0.0,
                q_offset=0, kv_len=None, sharded: bool = False) -> int:
    """Block size of the fused causal pair (`kernels.ops.causal_attention`)
    for a call of these shapes on `platform`, or 0 where the call keeps
    the XLA path.

    The pair covers causal self-attention on one TPU: no window, no
    softcap, no `kv_len`, `q_offset` 0 (training and full prefill), a
    head size the MXU lanes tile and a sequence the block divides. GSPMD
    cannot partition a `pallas_call`, so a call sharded over a mesh keeps
    XLA. The block is the largest of `FUSED_BLOCKS` that divides S.
    """
    if platform != "tpu" or sharded or not causal or window or logit_cap:
        return 0
    if kv_len is not None or not isinstance(q_offset, int) or q_offset:
        return 0
    if sq != skv or head_dim % 128:
        return 0
    return next((b for b in FUSED_BLOCKS if sq % b == 0), 0)


def gspmd_partitioned(mesh) -> bool:
    """Whether GSPMD partitions a call traced here over more than one
    device: an axis of the context mesh that is not manual has size > 1,
    or, outside any mesh context, `mesh` spans several devices. Inside a
    fully manual `shard_map` body every op is per device."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return mesh is not None and mesh.size > 1
    return any(t != jax.sharding.AxisType.Manual and am.shape[n] > 1
               for n, t in zip(am.axis_names, am.axis_types))


def attend(q, k, v, *, causal: bool, window: int = 0, logit_cap: float = 0.0,
           q_offset=0, kv_len=None, chunk: int = 1024, impl: str = "xla",
           settings: Any = None):
    """Dispatcher. Where `fused_block` admits the call on this backend,
    every impl runs the fused causal pair; otherwise `impl` chooses:
    xla (chunked scan, blocked for causal/window) | pallas |
    pallas_interpret (the forward kernel, a blockwise XLA backward).

    So on TPU `impl="xla"` takes the fused pair wherever it applies.
    "pallas_interpret" routes as on a TPU and interprets the kernels
    on the host.
    """
    if impl not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    interpret = impl == "pallas_interpret"
    blk = fused_block(
        "tpu" if interpret else jax.default_backend(),
        sq=q.shape[1], skv=k.shape[1], head_dim=q.shape[-1],
        causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, kv_len=kv_len,
        sharded=gspmd_partitioned(getattr(settings, "mesh", None)))
    if blk:
        from repro.kernels import ops as kops
        return kops.causal_attention(q, k, v, block=blk,
                                     interpret=interpret)
    if impl == "xla":
        import os
        Sq, Skv = q.shape[1], k.shape[1]
        if ((causal or window) and Sq == Skv and kv_len is None
                and isinstance(q_offset, int) and q_offset == 0
                and Sq > chunk
                and not os.environ.get("REPRO_NO_BLOCKED_ATTN")):
            return attend_blocked(q, k, v, causal=causal, window=window,
                                  logit_cap=logit_cap, chunk=chunk,
                                  settings=settings)
        return attend_chunked(q, k, v, causal=causal, window=window,
                              logit_cap=logit_cap, q_offset=q_offset,
                              kv_len=kv_len, chunk=chunk, settings=settings)
    from repro.kernels import ops as kops
    return kops.flash_attention(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        interpret=interpret)
