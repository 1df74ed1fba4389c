"""The dense decoder family (`"family": "dense"`): Llama/Qwen2-style
layers of grouped-query self-attention with rotary embeddings and
optional q/k/v bias, then a SwiGLU MLP, one block kind repeated over
depth.

A family module maps a configuration file (published key names) to the
program and to the plain reference. It exports:

  program_config(conf)          the program's ModelConfig
  flops_per_token(conf, T)      model FLOPs per trained token at sequence
                                length T (bench/flops.py), recomputation
                                not counted
  param_shapes(conf)            the weights' tree in the program's layout
  blocks(conf)                  for each segment of that layout, the plain
                                float32 block function of each block
                                (bench/reference/model.py says how one is
                                called)
"""
from __future__ import annotations

from typing import Any, Dict

from bench import flops
from bench.reference import decoder


def program_config(conf: Dict[str, Any]):
    from repro.configs.base import ModelConfig
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
    per_layer = (flops.attention_params(c)
                 + flops.glu_params(c, c["intermediate_size"]))
    return c["num_hidden_layers"] * per_layer + flops.head_params(c)


def flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    L, H, Q = (c["num_hidden_layers"], c["num_attention_heads"],
               c["head_dim"])
    return flops.palm(matmul_params(c), L * H * Q * seq_len)


def param_shapes(c: Dict[str, Any]):
    L = c["num_hidden_layers"]
    block = dict(decoder.attention_shapes(c, L),
                 mlp=decoder.glu_shapes(c, L, c["intermediate_size"]),
                 mlp_norm={"scale": decoder.shape_maker(c)(
                     L, c["hidden_size"])})
    return decoder.model_shapes(c, [{"b0": block}])


def layer(x, p: Dict[str, Any], c: Dict[str, Any], quant, q_block: int):
    """One decoder layer; x (B, S, D) f32, p the layer's f32 leaves."""
    x = decoder.self_attention(x, p, c, quant, q_block)
    h = decoder.rms_norm(x, p["mlp_norm"]["scale"], c["rms_norm_eps"])
    return x + decoder.glu(h, p["mlp"], quant)


def blocks(c: Dict[str, Any]):
    return [[layer]]
