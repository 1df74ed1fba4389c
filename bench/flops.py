"""Model FLOPs per trained token, after PaLM (Chowdhery et al. 2022,
appendix B): 6 N + 12 L H Q T.

N counts the matmul weights a token passes through, the output head
included and the input embedding lookup left out; L layers, H query
heads of size Q, T keys each query attends to. Recomputed operations
are not counted, so the number is the same for every placement of the
activations. Each family (`bench/families/<family>.py`) counts its own
N and attention from these pieces, in its `flops_per_token`.
"""
from __future__ import annotations

from typing import Any, Dict


def attention_params(c: Dict[str, Any]) -> int:
    """q/k/v/o projection weights of one grouped-query attention layer,
    from the configuration file's published keys."""
    d = c["hidden_size"]
    h, kv, q = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    return d * h * q + 2 * d * kv * q + h * q * d


def glu_params(c: Dict[str, Any], ff: int) -> int:
    """Weights of one gated MLP of width `ff`."""
    return 3 * c["hidden_size"] * ff


def head_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["vocab_size"]


def palm(matmul_params: int, attended: int) -> float:
    """6 N + 12 x `attended`, the sum over layers of query heads x head
    size x keys attended (H Q T for a layer of full causal attention)."""
    return 6.0 * matmul_params + 12.0 * attended
