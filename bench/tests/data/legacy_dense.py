"""The dense-only pieces that the family modules replaced, kept as they
were so that tests can show the replacement changes nothing: the
configuration's mapping to the program's ModelConfig, the FLOPs per
token, and the plain reference that handled one segment of one block
kind (its loss and gradients, the float8 control and the residual
fault). AdamW and the three steps were shared and are not copied."""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn
# queries per attention block and rows per head block: pieces that fit
# beside nothing else on one chip at the benchmark's widths
Q_BLOCK = 1024
HEAD_ROWS = 512


# ------------------------------------------------------------ matmuls

def _q8(x):
    """Round to float8_e4m3 with one scale for the whole tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return jnp.einsum(spec, _q8(a), _q8(b), precision=HIGHEST)


def _einsum_fp8_fwd(spec, a, b):
    return _einsum_fp8(spec, a, b), (a, b)


def _einsum_fp8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(
        lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
        _q8(a), _q8(b))
    return vjp(_q8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def mm(spec: str, a, b, quant: Optional[str]):
    if quant is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if quant == "fp8":
        return _einsum_fp8(spec, a, b)
    raise ValueError(f"unknown quant {quant!r}")


# ------------------------------------------------------------- blocks

def rms_norm(x, scale, eps: float):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * (1.0 + scale)


def rope(x, theta: float):
    """x: (B, S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, quant, q_block: int):
    """Causal softmax attention; q (B,S,H,D), k/v (B,S,Hkv,D). Query
    blocks are recomputed in backward, so no (S, S) score tensor of the
    whole sequence is kept."""
    B, S, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)            # head h reads kv head h // G
    v = jnp.repeat(v, G, axis=2)
    qb = min(q_block, S)
    nb = S // qb

    @jax.checkpoint
    def block(i, qi):
        s = mm("bqhd,bkhd->bhqk", qi, k, quant) / math.sqrt(D)
        rows = i * qb + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, quant)

    qs = q.reshape(B, nb, qb, H, D).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qs))
    return out.swapaxes(0, 1).reshape(B, S, H, D)


def layer(x, p: Dict[str, Any], c: Dict[str, Any], quant, q_block: int):
    """One decoder layer; x (B, S, D) f32, p the layer's f32 leaves."""
    eps = c["rms_norm_eps"]
    a = p["attn"]
    h = rms_norm(x, p["norm"]["scale"], eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], quant)
    k = mm("bsd,dhk->bshk", h, a["wk"], quant)
    v = mm("bsd,dhk->bshk", h, a["wv"], quant)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, c["rope_theta"])
    k = rope(k, c["rope_theta"])
    o = attention(q, k, v, quant, q_block)
    x = x + mm("bshk,hkd->bsd", o, a["wo"], quant)
    h = rms_norm(x, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    g = mm("bsd,df->bsf", h, m["w_gate"], quant)
    u = mm("bsd,df->bsf", h, m["w_in"], quant)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_out"], quant)


def head_nll(x, fnorm, unembed, labels, c, quant):
    """Summed next-token NLL of rows x (R, D) against labels (R,); labels
    below 0 are not counted."""
    h = rms_norm(x, fnorm, c["rms_norm_eps"])
    logits = mm("rd,dv->rv", h, unembed[:, :c["vocab_size"]], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, lse - picked, 0.0))


# -------------------------------------------------------------- model

def padded_vocab(c: Dict[str, Any]) -> int:
    return -(-c["vocab_size"] // 256) * 256


def param_shapes(c: Dict[str, Any]):
    """The weights' tree: the program's layout, from the configuration.
    Decoder leaves are stacked over layers on their first axis."""
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.dtype(c["torch_dtype"]))  # noqa: E731
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, KV, Q = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    V = padded_vocab(c)
    attn = {"wq": f(L, D, H, Q), "wk": f(L, D, KV, Q), "wv": f(L, D, KV, Q),
            "wo": f(L, H, Q, D)}
    if c["attention_bias"]:
        attn.update(bq=f(L, H, Q), bk=f(L, KV, Q), bv=f(L, KV, Q))
    block = {"norm": {"scale": f(L, D)}, "attn": attn,
             "mlp": {"w_in": f(L, D, F), "w_gate": f(L, D, F),
                     "w_out": f(L, F, D)},
             "mlp_norm": {"scale": f(L, D)}}
    return {"final_norm": {"scale": f(D)}, "embed": f(V, D),
            "unembed": f(D, V), "segments": [{"b0": block}]}


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_at(stack, l):
    return _f32(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
        stack))


class Reference:
    """Loss, gradients and AdamW steps of the plain decoder.

    `c` is the configuration (published key names); attention runs in
    blocks of `q_block` queries and the head in blocks of `head_rows`
    rows (each at most the whole)."""

    def __init__(self, c: Dict[str, Any], quant: Optional[str] = None,
                 residual_from: Optional[Tuple[int, int]] = None,
                 q_block: int = Q_BLOCK, head_rows: int = HEAD_ROWS):
        self.c = dict(c)
        self.quant = quant
        self.residual_from = residual_from
        self.head_rows = head_rows
        quant_ = quant
        cc = self.c

        @jax.jit
        def embed(table, tokens):
            return table[tokens].astype(jnp.float32)

        @jax.jit
        def layer_fwd(x, stack, l):
            return layer(x, _layer_at(stack, l), cc, quant_, q_block)

        @jax.jit
        def layer_bwd(x, stack, l, g):
            _, vjp = jax.vjp(lambda x_, p_: layer(x_, p_, cc, quant_,
                                                  q_block),
                             x, _layer_at(stack, l))
            return vjp(g)

        @functools.partial(jax.jit, donate_argnums=(5,))
        def head_bwd(x, fnorm, unembed, labels, scale, d_unembed):
            f = lambda x_, n_, u_: head_nll(x_, n_, u_, labels, cc, quant_)
            nll, vjp = jax.vjp(f, x, fnorm.astype(jnp.float32),
                               unembed.astype(jnp.float32))
            dx, dn, du = vjp(scale)
            return nll, dx, dn, d_unembed + du

        self._embed = embed
        self._layer_fwd = layer_fwd
        self._layer_bwd = layer_bwd
        self._head_bwd = head_bwd

    def loss_and_grads(self, params, batch):
        """(mean loss, f32 gradients in the params' layout)."""
        (seg,) = params["segments"]
        (blk,) = seg.values()
        L = jax.tree.leaves(blk)[0].shape[0]
        tokens = jnp.asarray(batch["tokens"])
        labels = np.asarray(batch["labels"])
        count = max(int(np.sum(labels >= 0)), 1)
        xs = [self._embed(params["embed"], tokens)]
        for l in range(L):
            xs.append(self._layer_fwd(xs[-1], blk, l))
        B, S, D = xs[-1].shape
        xf = xs[-1].reshape(B * S, D)
        lf = jnp.asarray(labels.reshape(B * S))
        rb = min(self.head_rows, B * S)
        nll = 0.0
        dx_parts = []
        d_norm = None
        d_unembed = jnp.zeros(params["unembed"].shape, jnp.float32)
        scale = jnp.float32(1.0 / count)
        for r in range(0, B * S, rb):
            n, dx, dn, d_unembed = self._head_bwd(
                xf[r:r + rb], params["final_norm"]["scale"],
                params["unembed"], lf[r:r + rb], scale, d_unembed)
            nll += float(n)
            dx_parts.append(dx)
            d_norm = dn if d_norm is None else d_norm + dn
        del xf
        g = jnp.concatenate(dx_parts).reshape(B, S, D)
        del dx_parts
        stacked = jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), blk)
        saved = {l: l for l in range(L)}
        if self.residual_from is not None:
            saved[self.residual_from[0]] = self.residual_from[1]
        for l in reversed(range(L)):
            g, dl = self._layer_bwd(xs[saved[l]], blk, l, g)
            stacked = _set_layer(stacked, dl, l)
        del xs
        d_embed = _embed_grad(params["embed"].shape, tokens, g)
        grads = {"embed": d_embed, "final_norm": {"scale": d_norm},
                 "segments": [{next(iter(seg)): stacked}],
                 "unembed": d_unembed}
        if jax.tree.structure(grads) != jax.tree.structure(params):
            raise ValueError("the reference handles an embedding, one "
                             "stack of decoder layers, a final norm and "
                             "a head; the weights hold "
                             f"{jax.tree.structure(params)}")
        return nll / count, grads


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_layer(stack, layer_grads, l):
    return jax.tree.map(lambda a, b: a.at[l].set(b), stack, layer_grads)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_grad(shape, tokens, g):
    return jnp.zeros(shape, jnp.float32).at[tokens].add(g)


# ----------------------------------------------- mapping and FLOPs

def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    if conf["family"] != "dense":
        raise ValueError(f"family {conf['family']!r} has no mapping here")
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"], qkv_bias=conf["attention_bias"],
        rope_theta=conf["rope_theta"], act=conf["hidden_act"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"]).validate()


def matmul_params(c: Dict[str, Any]) -> int:
    d = c["hidden_size"]
    h, kv, q = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    ff = c["intermediate_size"]
    per_layer = d * h * q + 2 * d * kv * q + h * q * d + 3 * d * ff
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    L, H, Q = (c["num_hidden_layers"], c["num_attention_heads"],
               c["head_dim"])
    return 6.0 * matmul_params(c) + 12.0 * L * H * Q * seq_len
