"""Model FLOPs per trained token, after PaLM (Chowdhery et al. 2022,
appendix B): 6 N + 12 L H Q T.

N counts every matmul weight, the output head included and the input
embedding lookup left out; L layers, H query heads of size Q, T tokens
per sequence. Recomputed operations are not counted, so the number is
the same for every placement of the activations.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights of a dense decoder, from the configuration file's
    published keys."""
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
