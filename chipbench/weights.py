"""Weights from the seed, made by the benchmark on the device in one jitted
call, so that the program and the plain reference start from the same
numbers and neither takes them from the other.

A configuration's ``init`` is a list of ``[path regex, kind, value]`` rules;
the first that matches a leaf's path decides it: ``normal`` (std ``value``),
``he_normal`` (std ``sqrt(2 / fan_in)``, gain ``value``), ``const``. Each
leaf's stream is keyed on its path, not on its position in the tree.
"""

from __future__ import annotations

import math
import re
import zlib

import jax
import jax.numpy as jnp


def path_of(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in key_path)


def flatten(tree) -> dict:
    """``{path: leaf}`` of a nested mapping of arrays."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_of(p): leaf for p, leaf in leaves}


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, path, shape, dtype, rules):
    for pattern, kind, value in rules:
        if re.search(pattern, path):
            break
    else:
        raise ValueError(f"no init rule matches parameter {path!r}")
    if kind == "const":
        return jnp.full(shape, value, dtype)
    key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    std = value
    if kind == "he_normal":
        std = value * math.sqrt(2.0 / math.prod(shape[:-1]))
    elif kind != "normal":
        raise ValueError(f"unknown init kind {kind!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_flat(shapes: dict, rules, key) -> dict:
    """``{path: array}`` for ``{path: ShapeDtypeStruct}``; jit this."""
    return {p: _leaf(key, p, s.shape, s.dtype, rules)
            for p, s in shapes.items()}


def make_like(tree, rules, key):
    """The same numbers in the shape of ``tree`` (a pytree of arrays or
    ShapeDtypeStructs); jit this with the tree's shardings as outputs."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(treedef, [
        _leaf(key, path_of(p), leaf.shape, leaf.dtype, rules)
        for p, leaf in leaves])
