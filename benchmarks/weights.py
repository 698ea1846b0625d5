"""Weights from the seed, made on the device in one jitted call, in the
float32 the trainer keeps its masters in. The same leaves go to ``fit``
(``init_params=``) and to the plain reference, so neither side takes
anything the other has made."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

STD = 0.02


def base_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31
    )


def leaf_paths(tree) -> list[str]:
    """``a/b/c`` names of the leaves, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path) for path, _ in flat]


def _split(flat, names, shapes):
    """Cut one flat vector of N(0, STD) draws into the leaves. Norm scales
    sit around one, everything else around nought; biases are not left at
    nought, so that every leaf's gradient path is live."""
    out, start = [], 0
    for name, shape in zip(names, shapes):
        size = math.prod(shape)
        leaf = flat[start:start + size].reshape(shape)
        out.append(1.0 + leaf if name.endswith("scale") else leaf)
        start += size
    return out


def generate(shapes, seed: int, sharding=None):
    """The whole tree of ``shapes`` (a pytree of ShapeDtypeStructs or
    arrays) in one jitted call: one draw for all leaves, and the key an
    argument, so that every seed runs the same cached program."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    names = leaf_paths(shapes)
    dims = [tuple(l.shape) for l in leaves]
    total = sum(math.prod(d) for d in dims)

    def make(key):
        flat = STD * jax.random.normal(key, (total,), jnp.float32)
        return treedef.unflatten(_split(flat, names, dims))

    return jax.jit(make, out_shardings=sharding)(base_key(seed))


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def leaf_norms(tree) -> dict:
    """``{path: l2 norm}`` of every leaf, float32, as device scalars."""
    norms = jax.jit(lambda t: [_norm(x) for x in jax.tree_util.tree_leaves(t)])
    return dict(zip(leaf_paths(tree), norms(tree)))


def change_norms(params, start) -> dict:
    """``{path: ||leaf - the same leaf of start||}``, as device scalars."""
    norms = jax.jit(lambda a, b: [
        _norm(x - y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                     jax.tree_util.tree_leaves(b))
    ])
    return dict(zip(leaf_paths(params), norms(params, start)))
