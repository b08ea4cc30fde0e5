"""Weights and gradient trees made on the device from ``--seed``.

The benchmark makes every number it feeds the program, so that the plain
reference can make the same numbers again without taking anything the
program made.  The tree's structure and shapes come from the program's
``jax.eval_shape`` of its init; the values come from here, by leaf name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: leaf-name rules: (kind, scale).  "normal" leaves are N(0, 1)·scale,
#: with scale None meaning fan-in ** -0.5 of the unstacked leaf.
RULES = {
    "embed": ("normal", 0.02),
    "A_log": ("log_uniform", (1.0, 16.0)),
    "D": ("one_plus", 0.1),
    "dt_bias": ("normal", 0.5),
}
#: unstacked 1-D leaves not in RULES (norm weights, biases): N(0, 0.05)
VECTOR_SCALE = 0.05
#: the depthwise causal-conv kernels: (width, channels)
CONV_SCALE = 0.2

#: roots whose leaves carry a leading layer-stack axis
STACKED = frozenset({"layers"})


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any seed that fits 64 bits (the driver's seeds
    pass 2**31)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _leaf_info(path) -> tuple[str, bool]:
    keys = [p.key for p in path if hasattr(p, "key")]
    return (keys[-1] if keys else ""), bool(keys) and keys[0] in STACKED


def _leaf(name: str, stacked: bool, shape, dtype, key):
    unstacked = shape[1:] if stacked else shape
    kind, arg = RULES.get(name, (None, None))
    if kind == "log_uniform":
        lo, hi = arg
        u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        return jnp.log(u).astype(dtype)
    if kind == "one_plus":
        return (1.0 + arg * jax.random.normal(key, shape)).astype(dtype)
    if kind == "normal":
        return (arg * jax.random.normal(key, shape)).astype(dtype)
    if len(unstacked) < 2:
        return (VECTOR_SCALE * jax.random.normal(key, shape)).astype(dtype)
    if name.startswith("conv_"):
        return (CONV_SCALE * jax.random.normal(key, shape)).astype(dtype)
    scale = unstacked[-2] ** -0.5
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def init_params(shapes, key):
    """Values for the tree ``shapes`` (arrays or ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        name, stacked = _leaf_info(path)
        out.append(_leaf(name, stacked, s.shape, s.dtype,
                         jax.random.fold_in(key, i)))
    return jax.tree.unflatten(treedef, out)


def gradient_tree(shapes, key, world: int):
    """One fp32 gradient per rank for every leaf of ``shapes``: each leaf
    becomes ``(world, *shape)``, N(0, 1) scaled per leaf so that leaves
    differ in magnitude as real gradients do."""
    flat, treedef = jax.tree.flatten(shapes)
    out = []
    for i, s in enumerate(flat):
        k = jax.random.fold_in(key, i)
        scale = 2.0 ** ((i % 7) - 3)
        out.append(scale * jax.random.normal(k, (world,) + tuple(s.shape),
                                             jnp.float32))
    return jax.tree.unflatten(treedef, out)
