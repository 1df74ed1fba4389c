"""Mixture-of-Experts FFN with true expert parallelism.

Two execution paths with identical math:
  * local: every device computes all experts (smoke tests / 1-device CPU);
  * ep: `shard_map` over the mesh — experts sharded over the `model` axis,
    tokens over the data axes; each shard gathers its local experts' tokens
    into a capacity buffer, runs batched GEMMs, and a psum over `model`
    combines the partial outputs. This is the real EP dataflow (the psum is
    the combine all-reduce), so the dry-run roofline sees honest collectives
    and honest FLOPs (capacity-padded, not E-times overcounted).

Routing: full-softmax then top-k, renormalised (Mixtral-style); capacity
factor with drop (GShard-style, per data shard). Aux losses: load-balance
(Switch) + router z-loss.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from repro.models.layers import activation, dense_init


@dataclass(frozen=True)
class MoESettings:
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    act: str = "silu"


def init_moe(key, d_model, d_ff, num_experts, dtype):
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d_model, num_experts), d_model,
                             jnp.float32),
        "w_in": dense_init(ks[1], (num_experts, d_model, d_ff), d_model, dtype),
        "w_gate": dense_init(ks[2], (num_experts, d_model, d_ff), d_model,
                             dtype),
        "w_out": dense_init(ks[3], (num_experts, d_ff, d_model), d_ff, dtype),
    }


def _route(x, router_w, settings: MoESettings):
    """Returns (eids (T,k) int32, weights (T,k) f32, aux losses)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, eids = jax.lax.top_k(probs, settings.top_k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch load-balance loss + z-loss.
    E = settings.num_experts
    me = probs.mean(axis=0)                                    # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(
        1.0 / eids.size)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return eids, weights, {"moe_lb": lb_loss, "moe_z": z_loss}


def _expert_ranks(eids_flat, num_experts):
    """Rank of each routed token within its expert, memory-light
    (sort-based, no (T*k, E) one-hot)."""
    tk = eids_flat.shape[0]
    order = jnp.argsort(eids_flat, stable=True)
    counts = jnp.zeros((num_experts,), jnp.int32).at[eids_flat].add(1)
    starts = jnp.cumsum(counts) - counts                        # exclusive
    rank_sorted = jnp.arange(tk, dtype=jnp.int32) - starts[eids_flat[order]]
    rank = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted)
    return rank


def _expert_ffn(buf, p_in, p_gate, p_out, act_name):
    """buf: (E_local, C, D) capacity buffer -> same shape output."""
    act = activation(act_name)
    h = jnp.einsum("ecd,edf->ecf", buf, p_in)
    g = jnp.einsum("ecd,edf->ecf", buf, p_gate)
    from jax.ad_checkpoint import checkpoint_name
    h = checkpoint_name(act(g) * h, "moe_hidden")
    return jnp.einsum("ecf,efd->ecd", h, p_out)


def _moe_shard_body(x, eids, weights, w_in, w_gate, w_out, *,
                    settings: MoESettings, e0, num_local: int,
                    capacity: int, ep_axis: Optional[str]):
    """Per-shard MoE compute. x: (T, D) local tokens; w_*: local experts."""
    T, D = x.shape
    k = settings.top_k
    ef = eids.reshape(-1)                                       # (T*k,)
    rank = _expert_ranks(ef, settings.num_experts)
    local = (ef >= e0) & (ef < e0 + num_local)
    le = jnp.where(local, ef - e0, num_local)                   # OOB -> drop
    slot = jnp.where(local & (rank < capacity), rank, capacity)
    xk = jnp.repeat(x, k, axis=0)                               # (T*k, D)
    buf = jnp.zeros((num_local + 1, capacity + 1, D), x.dtype)
    buf = buf.at[le, slot].add(xk, mode="drop")
    buf = buf[:num_local, :capacity]
    out_buf = _expert_ffn(buf, w_in, w_gate, w_out, settings.act)
    out_buf = jnp.pad(out_buf, ((0, 1), (0, 1), (0, 0)))
    yk = out_buf[le, slot] * weights.reshape(-1)[:, None].astype(x.dtype)
    y = yk.reshape(T, k, D).sum(axis=1)
    if ep_axis is not None:
        y = jax.lax.psum(y, ep_axis)
    return y


def apply_moe(params, x, settings: MoESettings, *,
              mesh=None, ep_axis: Optional[str] = None,
              dp_axes: Tuple[str, ...] = ()):
    """x: (B, S, D) -> (y (B, S, D), aux dict)."""
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    eids, weights, aux = _route(xf, params["router"], settings)
    E, k = settings.num_experts, settings.top_k

    if mesh is None or ep_axis is None:
        # Dropless on the single-device path: an expert can receive at
        # most one assignment per token, so capacity B*S covers the
        # worst case. Capacity-factor drops here would make the output
        # depend on batch composition — a full-sequence forward and a
        # prefill of the same prefix would drop *different* tokens,
        # breaking prefill/decode consistency (the serving invariant).
        # Cost: the dispatch buffer is (E, B*S, D) instead of
        # (E, ~B*S*k/E, D); at large single-device scale a sort-based
        # ragged dispatch would avoid the E-fold worst case (capacity
        # must be trace-static under jit, so it cannot adapt to the
        # routed load). The EP shard_map path below keeps GShard
        # capacity semantics.
        capacity = B * S
        y = _moe_shard_body(xf, eids, weights, params["w_in"],
                            params["w_gate"], params["w_out"],
                            settings=settings, e0=0, num_local=E,
                            capacity=capacity, ep_axis=None)
        return y.reshape(B, S, D), aux

    ep = mesh.shape[ep_axis]
    assert E % ep == 0, (E, ep)
    num_local = E // ep
    dp = math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else 1
    local_tokens = (B * S) // dp
    capacity = int(math.ceil(local_tokens * k / E *
                             settings.capacity_factor))

    def body(xl, el, wl, w_in, w_gate, w_out):
        e0 = jax.lax.axis_index(ep_axis) * num_local
        return _moe_shard_body(xl, el, wl, w_in, w_gate, w_out,
                               settings=settings, e0=e0,
                               num_local=num_local, capacity=capacity,
                               ep_axis=ep_axis)

    dp_spec = P(dp_axes) if dp_axes else P(None)
    y = _shard_map(
        body, mesh=mesh,
        in_specs=(dp_spec, dp_spec, dp_spec,
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=dp_spec,
        check_vma=False,
    )(xf, eids, weights, params["w_in"], params["w_gate"], params["w_out"])
    return y.reshape(B, S, D), aux
