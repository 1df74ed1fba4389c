"""Public model API: build_model(config) -> ModelApi.

A ModelApi bundles the functional pieces every launcher/benchmark needs:
  init(rng)                          -> params
  forward(params, batch, settings)   -> (logits_f32, aux)        # full seq
  loss(params, batch, settings)      -> (scalar, metrics)
  prefill(params, batch, settings)   -> (last_logits, cache)
  decode_step(params, cache, batch, pos, settings) -> (logits, cache)
  input_specs(shape)                 -> batch of ShapeDtypeStructs

Embeddings note (DESIGN.md §2): input and output embeddings are stored
untied even for archs that tie them (sharding: the input table is gathered
row-wise, the output table is a vocab-sharded matmul; tying would force one
of the two into a pathological layout). The vocab is padded to a multiple of
256 and padded logits are masked to -inf before the softmax.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.layers import (dtype_of, embed_init, hint, init_norm,
                                 rms_norm, softcap)
from repro.models.transformer import (BlockDef, RunSettings, SegmentDef,
                                      apply_block, apply_block_decode,
                                      apply_block_decode_paged,
                                      build_segments, init_block, init_cache,
                                      remat_policy)

Params = Dict[str, Any]

MOE_LB_COEF = 0.01
MOE_Z_COEF = 0.001


@dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig
    segments: Tuple[SegmentDef, ...]
    enc_segments: Tuple[SegmentDef, ...]  # empty unless encoder-decoder
    init: Callable
    forward: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    decode_step_paged: Callable
    input_specs: Callable
    init_cache: Callable


# -------------------------------------------------------------- helpers

def _init_segments(key, segs, cfg, dtype):
    out = []
    for seg in segs:
        keys = jax.random.split(key, seg.n_repeat + 1)
        key = keys[0]
        def one(k, seg=seg):
            ks = jax.random.split(k, len(seg.blocks))
            return {f"b{i}": init_block(ks[i], b, cfg, dtype)
                    for i, b in enumerate(seg.blocks)}
        out.append(jax.vmap(one)(keys[1:]))
    return out


def _run_segments(x, seg_params, segs, cfg, settings, *, enc_states=None,
                  emit_cache=False, positions=None, cache_len=0,
                  hook_step=None, hook_base=0):
    """Apply all segments. Returns (x, caches, aux_totals).

    When the "spool" activation policy is active and a traced step
    counter is supplied (the jit engine's train step), each scanned
    layer is wrapped in the repro.core.hooks custom_vjp so its autograd
    residuals stream through the ActivationSpool instead of living in
    device memory for the whole step. `settings.spool_stages` (decoder
    stream only, i.e. hook_base == 0) may keep a subset of layers on
    device; a scanned stack is then split into contiguous runs because
    a scan body's residual structure must be uniform."""
    wrap = remat_policy(settings)
    aux_tot: Dict[str, jnp.ndarray] = {}
    caches = []
    hooked = (settings.activation_policy == "spool"
              and settings.hook_bridge is not None
              and hook_step is not None and not emit_cache)
    # Grad-tap mode rides the same per-layer custom_vjp machinery but
    # needs no spool offload: segments (or runs) that are not hooked get
    # a tap-only wrapper so the opt sink still sees every layer's grads.
    tapping = (settings.opt_sink is not None
               and hook_step is not None and not emit_cache)
    if hooked or tapping:
        from repro.core.hooks import (run_splits, spooled_scan_body,
                                      tapped_scan_body)
        step_f = jnp.asarray(hook_step, jnp.float32)
        mask = (settings.spool_stages
                if hooked and hook_base == 0 else None)
    layer0 = 0

    for seg, p_stack in zip(segs, seg_params):
        def body(x, p_layer, seg=seg):
            aux: Dict[str, jnp.ndarray] = {}
            cache_entries = {}
            for i, bdef in enumerate(seg.blocks):
                x, c = apply_block(bdef, p_layer[f"b{i}"], x, cfg, settings,
                                   positions=positions, enc_kv=enc_states,
                                   aux=aux)
                if emit_cache:
                    cache_entries[f"b{i}"] = _to_decode_cache(
                        bdef, c, cfg, cache_len)
            return x, (cache_entries if emit_cache else None, aux)

        if hooked or tapping:
            # enc_states must be an EXPLICIT custom_vjp input (a
            # closed-over differentiable value raises at trace time and
            # its cotangent would be lost), so cross-attention segments
            # carry (x, enc) through the scan — enc passes through
            # unchanged and its per-layer cotangents accumulate on the
            # backward carry exactly like the staged engine's enc_grad.
            def seg_fn(p_layer, carry_in, seg=seg):
                x_, enc_ = (carry_in if enc_states is not None
                            else (carry_in, None))
                aux: Dict[str, jnp.ndarray] = {}
                for i, bdef in enumerate(seg.blocks):
                    x_, _ = apply_block(bdef, p_layer[f"b{i}"], x_, cfg,
                                        settings, positions=positions,
                                        enc_kv=enc_, aux=aux)
                out = (x_, enc_) if enc_states is not None else x_
                return out, aux

            if hooked:
                wrapped = spooled_scan_body(seg_fn, settings.hook_bridge,
                                            mesh=settings.mesh,
                                            dp_axes=settings.dp_axes,
                                            tp_axis=settings.tp_axis,
                                            opt_sink=settings.opt_sink)
            if tapping:
                # remat_policy still applies to tap-only bodies so
                # "remat" keeps its memory profile under the tap
                tap_wrapped = tapped_scan_body(wrap(seg_fn),
                                               settings.opt_sink,
                                               mesh=settings.mesh)
            seg_mask = [hooked and (bool(mask[layer0 + i])
                        if mask is not None and layer0 + i < len(mask)
                        else True)
                        for i in range(seg.n_repeat)]
            carry = (x, enc_states) if enc_states is not None else x
            for start, end, offl in run_splits(seg_mask):
                p_run = jax.tree.map(lambda a: a[start:end], p_stack)
                if offl:
                    idxs = (jnp.arange(start, end, dtype=jnp.float32)
                            + (hook_base + layer0))

                    def scan_body(c, inp, wrapped=wrapped):
                        p_layer, idx = inp
                        return wrapped(p_layer, c, step_f, idx)

                    carry, aux_stack = jax.lax.scan(scan_body, carry,
                                                    (p_run, idxs))
                elif tapping:
                    idxs = (jnp.arange(start, end, dtype=jnp.float32)
                            + (hook_base + layer0))

                    def scan_body(c, inp, tap_wrapped=tap_wrapped):
                        p_layer, idx = inp
                        return tap_wrapped(p_layer, c, step_f, idx)

                    carry, aux_stack = jax.lax.scan(scan_body, carry,
                                                    (p_run, idxs))
                else:

                    def scan_body(c, p_layer, seg_fn=seg_fn):
                        return seg_fn(p_layer, c)

                    carry, aux_stack = jax.lax.scan(scan_body, carry,
                                                    p_run)
                for k, v in aux_stack.items():
                    aux_tot[k] = aux_tot.get(k, 0.0) + jnp.sum(v)
            x = carry[0] if enc_states is not None else carry
            caches.append(None)
            layer0 += seg.n_repeat
            continue

        body = wrap(body)
        x, (cache_stack, aux_stack) = jax.lax.scan(
            lambda c, p: body(c, p), x, p_stack)
        caches.append(cache_stack)
        for k, v in aux_stack.items():
            aux_tot[k] = aux_tot.get(k, 0.0) + jnp.sum(v)
        layer0 += seg.n_repeat
    return x, caches, aux_tot


def _to_decode_cache(bdef: BlockDef, cache, cfg: ModelConfig,
                     cache_len: int):
    """Convert a prefill cache entry to the decode layout.

    Attention caches are sized min(window, cache_len) (ring for windowed
    layers): token at position p lives at slot p % W, so a prefill of S
    tokens contributes its last W via a roll of (S - W) % W."""
    if bdef.mixer == "attn":
        k, v = cache
        S = k.shape[1]
        target = min(bdef.window, cache_len) if bdef.window else cache_len
        if S >= target:
            k, v = k[:, -target:], v[:, -target:]
            shift = (S - target) % target
            if shift:
                k = jnp.roll(k, shift, axis=1)
                v = jnp.roll(v, shift, axis=1)
        else:
            pad = [(0, 0), (0, target - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        return {"k": k, "v": v}
    if bdef.mixer == "cross":
        k, v = cache
        return {"k": k, "v": v}
    return cache  # rglru / ssm already in decode layout


def _embed_in(params, batch, cfg: ModelConfig, settings):
    dtype = dtype_of(settings.param_dtype)
    if cfg.input_kind == "embeddings":
        x = batch["embeddings"].astype(dtype)
        x = jnp.einsum("bsd,de->bse", x, params["frontend_proj"])
    else:
        x = params["embed"][batch["tokens"]]
    if cfg.scale_embed:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if not cfg.use_rope:
        S = x.shape[1]
        x = x + params["pos_embed"][:S][None].astype(dtype)
    # gathers from the vocab-sharded table come out with ambiguous layout;
    # pin batch to the dp axes so the whole stack keeps it (layers.hint).
    return hint(x, settings, "b", None, None)


def _head(params, x, cfg: ModelConfig, settings=None):
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    logits = logits.astype(jnp.float32)
    # batch over dp, vocab over tp — without this GSPMD materialised the
    # full-batch fp32 logits (40 GB/device) on the 256-chip dry-run.
    logits = hint(logits, settings, "b", None, "m")
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    # mask the padded vocab tail
    if cfg.padded_vocab != cfg.vocab_size:
        bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                         0.0, -1e30).astype(jnp.float32)
        logits = logits + bias
    return logits


def _ce_terms(logits, labels):
    """(sum nll, token count) — vocab-parallel-friendly label pick:
    take_along_axis is a gather along the tp-sharded vocab dim and
    makes GSPMD all-gather the logits; the masked reduction
    partitions cleanly (Megatron's vocab-parallel cross-entropy)."""
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vmask = (jnp.arange(logits.shape[-1],
                        dtype=labels.dtype)[None, None]
             == jnp.maximum(labels, 0)[..., None])
    picked = jnp.sum(jnp.where(vmask, logits, 0.0), axis=-1)
    return ((lse - picked) * mask).sum(), mask.sum()


def head_ce_terms(params, x, labels, cfg: ModelConfig, settings=None):
    """(sum nll, token count) of the LM head over final hidden states.

    With `settings.ce_chunk` dividing the sequence, the (B, S, V) fp32
    logits never materialise: each chunk's head matmul + CE runs under
    remat, so backward recomputes one chunk of logits at a time. At
    V=152k, S=4096 that is ~2.5 GB of fp32 logits per sequence (x3
    with AD buffers). Shared by the whole-step loss and the staged
    engine's head stage."""
    S = labels.shape[1]
    chunk = settings.ce_chunk if settings is not None else 0
    if not chunk or S % chunk or S <= chunk:
        return _ce_terms(_head(params, x, cfg, settings), labels)
    nc = S // chunk
    xc = x.reshape(x.shape[0], nc, chunk, -1)
    lc = labels.reshape(labels.shape[0], nc, chunk)

    @jax.checkpoint
    def chunk_terms(args):
        xi, li = args
        return _ce_terms(_head(params, xi, cfg, settings), li)

    nll, toks = jax.lax.map(chunk_terms,
                            (xc.swapaxes(0, 1), lc.swapaxes(0, 1)))
    return nll.sum(), toks.sum()


# -------------------------------------------------------------- build

def build_model(cfg: ModelConfig) -> ModelApi:
    cfg = cfg.validate()
    segs = tuple(build_segments(cfg))
    enc_segs: Tuple[SegmentDef, ...] = ()
    if cfg.family == "encdec":
        enc_cfg = dataclasses.replace(cfg, causal=False)
        enc_segs = tuple(build_segments(enc_cfg))
        dec_blocks = (BlockDef("attn", mlp=None),
                      BlockDef("cross", mlp="dense"))
        segs = (SegmentDef(dec_blocks, cfg.num_decoder_layers),)

    def init(rng) -> Params:
        dtype = dtype_of(cfg.dtype)
        ks = jax.random.split(rng, 8)
        params: Params = {"final_norm": init_norm(cfg.d_model, dtype)}
        if cfg.input_kind == "embeddings":
            eye = jnp.eye(cfg.d_model, dtype=jnp.float32)
            noise = 0.02 * jax.random.normal(ks[0],
                                             (cfg.d_model, cfg.d_model))
            params["frontend_proj"] = (eye + noise).astype(dtype)
        else:
            params["embed"] = embed_init(
                ks[0], (cfg.padded_vocab, cfg.d_model), dtype)
        params["unembed"] = embed_init(
            ks[1], (cfg.d_model, cfg.padded_vocab), dtype)
        if not cfg.use_rope:
            params["pos_embed"] = embed_init(
                ks[2], (cfg.max_position, cfg.d_model), dtype)
        params["segments"] = _init_segments(ks[3], segs, cfg, dtype)
        if enc_segs:
            params["enc_segments"] = _init_segments(ks[4], enc_segs,
                                                    dataclasses.replace(
                                                        cfg, causal=False),
                                                    dtype)
            params["enc_norm"] = init_norm(cfg.d_model, dtype)
        return params

    def _encode(params, batch, settings):
        from repro.core.hooks import ENC_STAGE_BASE
        enc_cfg = dataclasses.replace(cfg, causal=False)
        x = _embed_in(params, {"tokens": batch["enc_tokens"]}, enc_cfg,
                      settings)
        pos = jnp.arange(x.shape[1])
        x, _, _ = _run_segments(x, params["enc_segments"], enc_segs,
                                enc_cfg, settings, positions=pos,
                                hook_step=batch.get("_spool_step"),
                                hook_base=ENC_STAGE_BASE)
        return rms_norm(x, params["enc_norm"]["scale"], cfg.norm_eps)

    def _enc_states(params, batch, settings):
        if cfg.family == "encdec":
            return _encode(params, batch, settings)
        if cfg.family == "vlm":
            # stub frontend: precomputed patch embeddings at d_model
            return batch["enc_embeddings"].astype(dtype_of(cfg.dtype))
        return None

    def forward(params, batch, settings: RunSettings, *, emit_cache=False,
                cache_len=0):
        enc_states = _enc_states(params, batch, settings)
        x = _embed_in(params, batch, cfg, settings)
        positions = jnp.arange(x.shape[1]) if cfg.use_rope else None
        x, caches, aux = _run_segments(
            x, params["segments"], segs, cfg, settings,
            enc_states=enc_states, emit_cache=emit_cache,
            positions=positions, cache_len=cache_len or x.shape[1],
            hook_step=batch.get("_spool_step"))
        logits = _head(params, x, cfg, settings)
        return (logits, caches, aux) if emit_cache else (logits, aux)

    def forward_hidden(params, batch, settings: RunSettings):
        """Backbone only: final hidden states (pre-head), aux losses."""
        enc_states = _enc_states(params, batch, settings)
        x = _embed_in(params, batch, cfg, settings)
        positions = jnp.arange(x.shape[1]) if cfg.use_rope else None
        x, _, aux = _run_segments(
            x, params["segments"], segs, cfg, settings,
            enc_states=enc_states, positions=positions,
            cache_len=x.shape[1], hook_step=batch.get("_spool_step"))
        return x, aux

    def loss(params, batch, settings: RunSettings):
        labels = batch["labels"]
        x, aux = forward_hidden(params, batch, settings)
        nll, tokens = head_ce_terms(params, x, labels, cfg, settings)
        ce = nll / jnp.maximum(tokens, 1.0)
        total = ce
        metrics = {"ce": ce, "tokens": tokens}
        if "moe_lb" in aux:
            total = total + MOE_LB_COEF * aux["moe_lb"] \
                          + MOE_Z_COEF * aux["moe_z"]
            metrics.update(moe_lb=aux["moe_lb"], moe_z=aux["moe_z"])
        metrics["loss"] = total
        return total, metrics

    def prefill(params, batch, settings: RunSettings, *, emit_cache=True,
                cache_len=0):
        out = forward(params, batch, settings, emit_cache=emit_cache,
                      cache_len=cache_len)
        if emit_cache:
            logits, caches, _ = out
            return logits[:, -1:], caches
        logits, _ = out
        return logits[:, -1:], None

    def _decode_embed(params, batch, pos, settings: RunSettings):
        """Embed one decode token per row. pos: scalar or (B,) int32."""
        if cfg.input_kind == "embeddings":
            x = batch["embeddings"].astype(dtype_of(settings.param_dtype))
            x = jnp.einsum("bsd,de->bse", x, params["frontend_proj"])
        else:
            x = params["embed"][batch["tokens"]]
        if cfg.scale_embed:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if not cfg.use_rope:
            pos = jnp.asarray(pos)
            if pos.ndim == 1:           # per-row positions: (B, 1, D)
                x = x + params["pos_embed"][pos][:, None].astype(x.dtype)
            else:
                x = x + jax.lax.dynamic_slice_in_dim(
                    params["pos_embed"], pos, 1,
                    axis=0)[None].astype(x.dtype)
        return x

    def decode_step(params, cache, batch, pos, settings: RunSettings):
        """One token for the whole batch. batch: {"tokens": (B, 1)} (or
        {"embeddings"}). pos: scalar int32 position of this token, or a
        (B,) int32 vector of per-row positions (continuous batching:
        each serving slot decodes its own sequence)."""
        x = _decode_embed(params, batch, pos, settings)
        new_caches = []
        for seg, p_stack, c_stack in zip(segs, params["segments"], cache):
            def body(x1, inp, seg=seg):
                p_layer, c_layer = inp
                new_c = {}
                for i, bdef in enumerate(seg.blocks):
                    x1, nc = apply_block_decode(
                        bdef, p_layer[f"b{i}"], x1, c_layer[f"b{i}"], pos,
                        cfg, settings)
                    new_c[f"b{i}"] = nc
                return x1, new_c
            x, nc_stack = jax.lax.scan(body, x, (p_stack, c_stack))
            new_caches.append(nc_stack)
        logits = _head(params, x, cfg, settings)
        return logits, new_caches

    def decode_step_paged(params, pools, resident, tables, batch, pos,
                          settings: RunSettings):
        """One token per serving slot against a paged KV cache
        (repro.kvcache). Layers whose cache is pageable (full-attention)
        read/write the shared device page pools through each row's page
        table; the rest (ring attention, rglru/ssm state, cross K/V)
        keep per-slot dense entries in `resident`.

          pools:    per-segment {f"b{i}": {"k","v"}} page-pool stacks,
                    leading dim n_repeat, only for paged blocks.
          resident: per-segment {f"b{i}": cache} stacks for the rest.
          tables:   (B, max_pages) int32 physical page table per row.
          pos:      (B,) int32 per-row absolute positions.

        Returns (logits, new_pools, new_resident).
        """
        x = _decode_embed(params, batch, pos, settings)
        new_pools, new_resident = [], []
        for seg, p_stack, pool_stack, res_stack in zip(
                segs, params["segments"], pools, resident):
            def body(x1, inp, seg=seg):
                p_layer, pool_layer, res_layer = inp
                np_, nr_ = {}, {}
                for i, bdef in enumerate(seg.blocks):
                    bid = f"b{i}"
                    if bid in pool_layer:
                        x1, np_[bid] = apply_block_decode_paged(
                            bdef, p_layer[bid], x1, pool_layer[bid],
                            tables, pos, cfg, settings)
                    else:
                        x1, nr_[bid] = apply_block_decode(
                            bdef, p_layer[bid], x1, res_layer[bid], pos,
                            cfg, settings)
                return x1, (np_, nr_)
            x, (npool, nres) = jax.lax.scan(
                body, x, (p_stack, pool_stack, res_stack))
            new_pools.append(npool)
            new_resident.append(nres)
        logits = _head(params, x, cfg, settings)
        return logits, new_pools, new_resident

    def input_specs(shape: ShapeConfig, *, for_loss: bool = True):
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        bf16 = jnp.bfloat16
        if shape.kind == "decode":
            batch = ({"embeddings": jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                                         bf16)}
                     if cfg.input_kind == "embeddings"
                     else {"tokens": jax.ShapeDtypeStruct((B, 1), i32)})
            cache = jax.eval_shape(lambda: init_cache(cfg, B, S))
            return {"batch": batch, "cache": cache,
                    "pos": jax.ShapeDtypeStruct((), i32)}
        batch: Dict[str, Any] = {}
        if cfg.input_kind == "embeddings":
            batch["embeddings"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       bf16)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        if cfg.family == "vlm":
            batch["enc_embeddings"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq_len, cfg.d_model), bf16)
        if cfg.family == "encdec":
            batch["enc_tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        if for_loss and shape.kind == "train":
            batch["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        return {"batch": batch}

    return ModelApi(
        cfg=cfg, segments=segs, enc_segments=enc_segs, init=init,
        forward=forward, loss=loss, prefill=prefill,
        decode_step=decode_step, decode_step_paged=decode_step_paged,
        input_specs=input_specs,
        init_cache=lambda B, S, dtype=jnp.bfloat16: init_cache(
            cfg, B, S, dtype),
    )
