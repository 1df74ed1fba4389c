"""Model and optimizer state made on the device from `--seed`.

The benchmark, not the program, makes the weights: the reference then
makes the very same weights from the same seed without taking anything
the program produced. The tree has the program's layout (read from
`jax.eval_shape` of its init); each leaf is drawn by the rule that the
configuration file gives for the leaf's name, with a key folded from the
seed and the leaf's index, so one leaf can be made again alone.

Rules (`init` in the configuration file), by leaf name:
  {"std": s}          truncated normal (+-2 std) with std s
  {"fan_in": [axes]}  truncated normal with std 1/sqrt(product of the
                      sizes of those axes), the fan-in of a matmul weight
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any whole seed up to 2**63 (the low and high 32 bits
    both count)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_std(name: str, shape: Tuple[int, ...],
             rules: Dict[str, Dict]) -> float:
    last = name.rsplit("/", 1)[-1]
    if last not in rules:
        raise KeyError(f"no init rule for leaf {name!r} ({last!r}); the "
                       f"configuration file's `init` names {sorted(rules)}")
    rule = rules[last]
    if "std" in rule:
        return float(rule["std"])
    fan_in = math.prod(shape[a] for a in rule["fan_in"])
    return 1.0 / math.sqrt(fan_in)


def _draw(key, index, shape, dtype, std):
    k = jax.random.fold_in(key, index)
    x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
    return (x * std).astype(dtype)


#: one leaf alone, for making a leaf of the initial weights again
draw_leaf = jax.jit(_draw, static_argnums=(2, 3, 4))


def leaf_specs(shapes) -> List[Tuple[str, Any]]:
    """(name, ShapeDtypeStruct) of every leaf, in flattening order."""
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return [(leaf_name(p), s) for p, s in flat]


def make_params(seed: int, shapes, rules: Dict[str, Dict]):
    """Every leaf of `shapes` (a tree of ShapeDtypeStructs), drawn on the
    device in one jitted call."""
    specs = leaf_specs(shapes)
    treedef = jax.tree.structure(shapes)

    @jax.jit
    def make(key):
        return jax.tree.unflatten(treedef, [
            _draw(key, i, s.shape, s.dtype, leaf_std(n, s.shape, rules))
            for i, (n, s) in enumerate(specs)])

    return make(seed_key(seed))
