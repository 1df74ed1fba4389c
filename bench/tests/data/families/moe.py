"""A test family: leading dense layers, then mixture-of-experts layers
as the program runs them on one device: a float32 router, a softmax over
all experts, the top k renormalised, every routed token served
(dropless), shared experts added, and the Switch balance loss and the
router z-loss added to the loss with the program's coefficients. The
program lays it out as two segments. It joins the benchmark as files
only: this module, under bench/families/, and a configuration naming it.
Key names are DeepSeek's (`first_k_dense_replace`, `n_routed_experts`,
`moe_intermediate_size`, ...)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import flops, harness
from bench.reference import decoder
from bench.reference.model import mm

dense = harness.family({"family": "dense"})

#: the program's coefficients of its balance and z-losses
LB_COEF = 0.01
Z_COEF = 0.001


def program_config(conf):
    return dataclasses.replace(
        dense.program_config(conf), family="moe",
        d_ff=conf["moe_intermediate_size"],
        moe_num_experts=conf["n_routed_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_shared_experts=conf["n_shared_experts"],
        moe_first_dense_layers=conf["first_k_dense_replace"],
        moe_dense_ff=conf["intermediate_size"]).validate()


def flops_per_token(c, seq_len: int) -> float:
    """A token passes through the router, its top k experts and the
    shared ones: the other experts' weights are not counted."""
    L, H, Q = (c["num_hidden_layers"], c["num_attention_heads"],
               c["head_dim"])
    n_dense = c["first_k_dense_replace"]
    ff = c["moe_intermediate_size"]
    expert_layer = (flops.attention_params(c)
                    + c["hidden_size"] * c["n_routed_experts"]
                    + (c["num_experts_per_tok"] + c["n_shared_experts"])
                    * flops.glu_params(c, ff))
    dense_layer = (flops.attention_params(c)
                   + flops.glu_params(c, c["intermediate_size"]))
    n = (n_dense * dense_layer + (L - n_dense) * expert_layer
         + flops.head_params(c))
    return flops.palm(n, L * H * Q * seq_len)


def param_shapes(c):
    n_dense = c["first_k_dense_replace"]
    L = c["num_hidden_layers"] - n_dense
    D, E, F = c["hidden_size"], c["n_routed_experts"], c["moe_intermediate_size"]
    f = decoder.shape_maker(c)
    first = dense.param_shapes(dict(c, num_hidden_layers=n_dense))
    block = dict(decoder.attention_shapes(c, L),
                 moe={"router": jax.ShapeDtypeStruct((L, D, E), jnp.float32),
                      "w_in": f(L, E, D, F), "w_gate": f(L, E, D, F),
                      "w_out": f(L, E, F, D)},
                 mlp_norm={"scale": f(L, D)})
    if c["n_shared_experts"]:
        block["moe_shared"] = decoder.glu_shapes(
            c, L, F * c["n_shared_experts"])
    return decoder.model_shapes(c, first["segments"] + [{"b0": block}])


def expert_layer(x, p, c, quant, q_block):
    """Attention, then the expert MLP; returns (x, the loss terms)."""
    x = decoder.self_attention(x, p, c, quant, q_block)
    h = decoder.rms_norm(x, p["mlp_norm"]["scale"], c["rms_norm_eps"])
    B, S, D = h.shape
    t = h.reshape(B * S, D)
    m = p["moe"]
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    logits = mm("td,de->te", t, m["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, ids = jax.lax.top_k(probs, k)
    weights = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    rows = jnp.arange(B * S)[:, None]
    gates = jnp.zeros((B * S, E), jnp.float32).at[rows, ids].set(weights)
    # every expert on every token; the gates keep each token's top k
    up = mm("td,edf->etf", t, m["w_in"], quant)
    gate = mm("td,edf->etf", t, m["w_gate"], quant)
    out = mm("etf,efd->etd", jax.nn.silu(gate) * up, m["w_out"], quant)
    y = jnp.einsum("te,etd->td", gates, out,
                   precision=jax.lax.Precision.HIGHEST).reshape(B, S, D)
    if "moe_shared" in p:
        y = y + decoder.glu(h, p["moe_shared"], quant)
    share = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / ids.size)
    balance = E * jnp.sum(probs.mean(axis=0) * share)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return x + y, LB_COEF * balance + Z_COEF * z


def blocks(c):
    return dense.blocks(c) + [[expert_layer]]
