"""Weights made on the device in one jitted call from the seed.

The program tells only the names, shapes and types of its parameters (a tree
of ShapeDtypeStructs); every value comes from here, so the plain reference
can be handed the same arrays without taking anything the program has made.
Rule by leaf name: `scale` 1 + 0.02 n, `bias` 0.02 n, `embedding` / `wpe`
0.02 n, any other matrix n / sqrt(fan_in) with fan_in the second-last
dimension (stacked layers lead).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import seed_halves, seed_key


def leaf_paths(tree) -> list[str]:
    return [
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(tree)
    ]


def _leaf(key, name: str, shape, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        x = 1.0 + 0.02 * n
    elif last in ("bias", "embedding", "wpe") or len(shape) < 2:
        x = 0.02 * n
    else:
        x = n / math.sqrt(shape[-2])
    return x.astype(dtype)


def make_params(shapes, seed: int, dtype=None, shardings=None):
    """`shapes`: the program's parameter tree of shapes. One jitted call, the
    same program for every seed."""
    names = leaf_paths(shapes)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    def build(lo, hi):
        root = seed_key(lo, hi, stream=1)
        out = [
            _leaf(jax.random.fold_in(root, i), name, leaf.shape,
                  dtype if dtype is not None else leaf.dtype)
            for i, (name, leaf) in enumerate(zip(names, leaves))
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(*seed_halves(seed))


def flat(params) -> dict:
    """{path: array}: the layout the plain reference reads."""
    return dict(zip(leaf_paths(params), jax.tree_util.tree_leaves(params)))
