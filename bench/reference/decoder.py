"""Plain float32 pieces of a decoder block, shared by the families.

Each piece follows the published description of a Llama/Qwen2-style
decoder: grouped-query self-attention with rotary embeddings
(rotate-half convention), optional q/k/v bias and an optional sliding
window, and a SwiGLU MLP, each behind its own RMSNorm with a residual
around it. A family's block function (`bench/families/<family>.py`)
composes them; the shapes they read are those of the program's layout.
Nothing here imports the program.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference.model import mm, padded_vocab, rms_norm


def shape_maker(c: Dict[str, Any]) -> Callable:
    """ShapeDtypeStruct maker in the configuration's parameter dtype."""
    dt = jnp.dtype(c["torch_dtype"])
    return lambda *s: jax.ShapeDtypeStruct(s, dt)


def attention_shapes(c: Dict[str, Any], L: int) -> Dict[str, Any]:
    """A stack of L self-attention sublayers: the q/k/v/o projections,
    their biases where `attention_bias` says, and the norm before."""
    f = shape_maker(c)
    D, H, KV, Q = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    attn = {"wq": f(L, D, H, Q), "wk": f(L, D, KV, Q), "wv": f(L, D, KV, Q),
            "wo": f(L, H, Q, D)}
    if c["attention_bias"]:
        attn.update(bq=f(L, H, Q), bk=f(L, KV, Q), bv=f(L, KV, Q))
    return {"norm": {"scale": f(L, D)}, "attn": attn}


def glu_shapes(c: Dict[str, Any], L: int, ff: int) -> Dict[str, Any]:
    """A stack of L SwiGLU weight triples of width `ff`."""
    f = shape_maker(c)
    D = c["hidden_size"]
    return {"w_in": f(L, D, ff), "w_gate": f(L, D, ff), "w_out": f(L, ff, D)}


def rope(x, theta: float):
    """x: (B, S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, quant, q_block: int, window: int = 0):
    """Causal softmax attention; q (B,S,H,D), k/v (B,S,Hkv,D). With
    `window` > 0, query i sees keys i - window + 1 .. i. Query blocks
    are recomputed in backward, so no (S, S) score tensor of the whole
    sequence is kept."""
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
        seen = jnp.arange(S)[None, :] <= rows
        if window:
            seen &= jnp.arange(S)[None, :] > rows - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, quant)

    qs = q.reshape(B, nb, qb, H, D).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qs))
    return out.swapaxes(0, 1).reshape(B, S, H, D)


def self_attention(x, p: Dict[str, Any], c: Dict[str, Any], quant,
                   q_block: int, window: int = 0):
    """x + attention(norm(x)); p holds `norm` and `attn`."""
    a = p["attn"]
    h = rms_norm(x, p["norm"]["scale"], c["rms_norm_eps"])
    q = mm("bsd,dhk->bshk", h, a["wq"], quant)
    k = mm("bsd,dhk->bshk", h, a["wk"], quant)
    v = mm("bsd,dhk->bshk", h, a["wv"], quant)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q, c["rope_theta"])
    k = rope(k, c["rope_theta"])
    o = attention(q, k, v, quant, q_block, window)
    return x + mm("bshk,hkd->bsd", o, a["wo"], quant)


def glu(h, m: Dict[str, Any], quant):
    """SwiGLU of normed activations h (B, S, D) through one weight
    triple."""
    g = mm("bsd,df->bsf", h, m["w_gate"], quant)
    u = mm("bsd,df->bsf", h, m["w_in"], quant)
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_out"], quant)


def model_shapes(c: Dict[str, Any], segments) -> Dict[str, Any]:
    """The whole weights' tree: the embedding, the final norm and the
    untied head around the family's `segments` (one dict of stacked
    blocks b0, b1, ... per segment)."""
    f = shape_maker(c)
    D, V = c["hidden_size"], padded_vocab(c)
    return {"final_norm": {"scale": f(D)}, "embed": f(V, D),
            "unembed": f(D, V), "segments": segments}
