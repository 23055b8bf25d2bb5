"""Weights from the seed, made on the device in one jitted call.

The program initializes its own weights from a fixed key; the benchmark
replaces them, before the optimizer state is built, with weights drawn
here from ``--seed`` in the program's own tree of names and shapes, in
float32 (the type the master weights are held in). The reference starts
from the same arrays. Matrices are N(0, 1/fan_in); BatchNorm scales are
1 + 0.1 N(0, 1); every other vector (biases) is 0.1 N(0, 1), so that no
leaf is identically zero and every bias is exercised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """--seed may exceed 2**31: fold the high bits in separately."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make(template, seed: int):
    """``template``: the program's ``variables["params"]`` (any pytree of
    arrays). Returns a tree of the same structure."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    specs = [(jax.tree_util.keystr(path), tuple(leaf.shape)) for path, leaf in leaves]

    def draw(key):
        out = []
        for i, (name, shape) in enumerate(specs):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if len(shape) >= 2:
                out.append(z / jnp.sqrt(jnp.float32(shape[-2])))
            elif "scale" in name:
                out.append(1.0 + 0.1 * z)
            else:
                out.append(0.1 * z)
        return out

    return jax.tree_util.tree_unflatten(treedef, jax.jit(draw)(seed_key(seed)))
