"""The comparison that decides `correct` for a training cell.

Three readings of the first steps are compared between the program and
the plain reference, each from the same seed, weights and batches:

  loss_gap     largest |program - reference| loss over the set-up steps
  grad_gap     first step's gradient as the optimizer gets it (after
               clipping), by the worst leaf: |norm_p - norm_r| over the
               larger of the reference's norm of that leaf and of the
               median leaf
  change_gap   change of the parameters over the set-up steps, by the
               worst leaf, measured the same way; leaves whose reference
               gradient is under a thousandth of the median leaf's are
               left out, since they move by round-off alone

A stacked leaf of the decoder layers counts as one leaf per layer.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bench.weights import draw_leaf, leaf_name, leaf_std, seed_key

#: leaves whose reference gradient norm is under this share of the
#: median leaf's are left out of change_gap
FLAT_GRAD = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap")


def _is_stacked(name: str) -> bool:
    return name.startswith("segments/")


@jax.jit
def _norms(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1), axis=1))


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _split(name: str, x) -> Dict[str, float]:
    if _is_stacked(name):
        return {f"{name}#{l}": float(v)
                for l, v in enumerate(np.asarray(_norms(x)))}
    return {name: float(_norm(x))}


def leaf_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """Norm of every leaf (one per layer for stacked leaves), times
    `scale`."""
    out: Dict[str, float] = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        for k, v in _split(leaf_name(path), x).items():
            out[k] = v * scale
    return out


@jax.jit
def _diff(a, b):
    return a.astype(jnp.float32) - b.astype(jnp.float32)


def change_norms(params, seed: int, rules: Dict[str, Dict]) \
        -> Dict[str, float]:
    """Norm of (params - the seed's initial weights) for every leaf; the
    initial weights are made again one leaf at a time."""
    key = seed_key(seed)
    out: Dict[str, float] = {}
    for i, (path, x) in enumerate(
            jax.tree_util.tree_flatten_with_path(params)[0]):
        name = leaf_name(path)
        std = leaf_std(name, tuple(x.shape), rules)
        p0 = draw_leaf(key, i, tuple(x.shape), x.dtype, std)
        out.update(_split(name, _diff(x, p0)))
        del p0
    return out


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                names: List[str]) -> Tuple[float, str]:
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, ""
    for n in names:
        d = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not math.isfinite(d):
            return math.inf, n
        if d > worst:
            worst, where = d, n
    return worst, where


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The compared numbers; `prog` and `ref` each hold `losses`,
    `grad_leaf_norms` and `change_leaf_norms`."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        raise ValueError(f"{len(lp)} program losses vs {len(lr)} reference")
    loss_gap = max(abs(a - b) if math.isfinite(a) else math.inf
                   for a, b in zip(lp, lr))
    gr = ref["grad_leaf_norms"]
    names = sorted(gr)
    grad_gap, grad_at = _worst_leaf(prog["grad_leaf_norms"], gr, names)
    med = float(np.median([gr[n] for n in names]))
    moving = [n for n in names if gr[n] >= FLAT_GRAD * med]
    change_gap, change_at = _worst_leaf(prog["change_leaf_norms"],
                                        ref["change_leaf_norms"], moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "grad_worst_leaf": grad_at,
            "change_worst_leaf": change_at,
            "leaves_left_out": sorted(set(names) - set(moving))}


def judge(nums: Dict[str, Any], limits: Dict[str, float]) \
        -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {number: {"value", "limit"}}): correct when every number
    that has a limit is finite and within it."""
    unknown = set(limits) - set(NUMBERS)
    if unknown or not limits:
        raise ValueError(f"limits {sorted(limits)} must name some of "
                         f"{NUMBERS}")
    shown = {}
    ok = True
    for k in (n for n in NUMBERS if n in limits):
        v, lim = float(nums[k]), float(limits[k])
        shown[k] = {"value": v, "limit": lim}
        ok = ok and math.isfinite(v) and v <= lim
    return ok, shown
