"""Random weights made by the benchmark from the seed, on the device.

The program under test is handed these weights; the plain reference reads
the same arrays.  Neither side's numbers therefore come from the program's
own initialisation.  The layout is the program's parameter tree, read from
its shapes with ``jax.eval_shape`` (no array of the program is made).

Values: norm gains ``1 + 0.1 N(0, 1)``, the embedding ``N(0, 1)`` and
every matrix ``N(0, 1) / sqrt(fan_in)``, so each layer keeps its input's
scale and the logits come out near unit scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that keeps all bits of a seed wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _leaf(key, name: str, shape, dtype):
    if "norm" in name:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name != "embed":
        x = x / math.sqrt(shape[-2])
    return x.astype(dtype)


def make(model, seed: int):
    """The model's parameter tree filled from ``seed`` in one jitted call.
    Leaves stacked over layers are drawn one layer at a time inside the
    call, so no full-size float32 temporary is ever live."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]

    def build(key):
        leaves = []
        for i, ((path, s), name) in enumerate(zip(flat, names)):
            k = jax.random.fold_in(key, i)
            if name.startswith("layers/"):
                keys = jax.random.split(k, s.shape[0])
                leaves.append(jax.lax.map(
                    lambda kk, s=s, name=name: _leaf(kk, name, s.shape[1:],
                                                     s.dtype), keys))
            else:
                leaves.append(_leaf(k, name, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def nbytes(tree) -> int:
    return int(sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)))
