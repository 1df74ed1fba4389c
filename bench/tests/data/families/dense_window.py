"""A test family: the dense decoder with local and global layers
alternating (`local_global_period` 2, as Gemma 2 alternates them): every
even layer sees the last `sliding_window` keys, every odd one all of
them. The program lays it out as one segment of two blocks. It joins
the benchmark as files only: this module, under bench/families/, and a
configuration naming it."""
from __future__ import annotations

import dataclasses
import functools

from bench import flops, harness
from bench.reference import decoder

dense = harness.family({"family": "dense"})


def program_config(conf):
    return dataclasses.replace(
        dense.program_config(conf), sliding_window=conf["sliding_window"],
        local_global_period=conf["local_global_period"]).validate()


def _local_layers(c) -> int:
    p = c["local_global_period"]
    return c["num_hidden_layers"] // p * (p - 1)


def flops_per_token(c, seq_len: int) -> float:
    """A local layer's queries attend to at most `sliding_window` keys."""
    L, H, Q = (c["num_hidden_layers"], c["num_attention_heads"],
               c["head_dim"])
    local = _local_layers(c)
    keys = local * min(c["sliding_window"], seq_len) + (L - local) * seq_len
    return flops.palm(dense.matmul_params(c), H * Q * keys)


def param_shapes(c):
    p = c["local_global_period"]
    one = dense.param_shapes(dict(c, num_hidden_layers=c["num_hidden_layers"]
                                  // p))["segments"][0]["b0"]
    return decoder.model_shapes(c, [{f"b{i}": one for i in range(p)}])


def layer(x, p, c, quant, q_block, window=0):
    x = decoder.self_attention(x, p, c, quant, q_block, window)
    h = decoder.rms_norm(x, p["mlp_norm"]["scale"], c["rms_norm_eps"])
    return x + decoder.glu(h, p["mlp"], quant)


def blocks(c):
    p = c["local_global_period"]
    local = functools.partial(layer, window=c["sliding_window"])
    return [[local] * (p - 1) + [layer]]
