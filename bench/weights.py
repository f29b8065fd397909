"""Weights from the seed, made by the benchmark on the device.

Every weight is a pure function of (seed, leaf name, layer): 32-bit
counter-based random bits turned into a uniform value with the variance
of the program's own initialiser (1/fan_in for matrices, 1 for the
embedding) and rounded once to the served type.  The program gets them
in its own parameter layout from one jitted call; the reference makes
the same values layer by layer, from the same names, without ever
touching what the program holds.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

#: the program's stacked-layer prefix in its dense parameter tree
STACK = "stack/stack/"


def base_key(seed: int):
    """A typed key from a seed of any size (seeds may exceed 2**31)."""
    return jax.random.fold_in(jax.random.key(seed % (1 << 31)),
                              seed >> 31)


def kind_of(name: str) -> str:
    if name.endswith("scale"):
        return "scale"
    if name == "embed/table":
        return "embed"
    return "matrix"


def leaf(key, name: str, layer, shape, dtype):
    """One weight array.  `layer` is -1 outside the layer stack; it may be
    a traced index."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer + 1)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5   # exact
    kind = kind_of(name)
    if kind == "scale":
        w = 1.0 + u * 0.2
    else:
        std = 1.0 if kind == "embed" else shape[0] ** -0.5
        w = u * (std * math.sqrt(12.0))
    return w.astype(dtype)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def make_params(shapes, seed: int):
    """The program's parameter tree (ShapeDtypeStructs in `shapes`,
    e.g. from jax.eval_shape(model.init, key)) filled from the seed in
    one jitted call, on the default device."""
    def build(key):
        def fill(path, sds):
            p = _path(path)
            if p.startswith(STACK):
                name = p[len(STACK):]
                return jax.lax.map(
                    lambda l: leaf(key, name, l, sds.shape[1:], sds.dtype),
                    jnp.arange(sds.shape[0]))
            return leaf(key, p, -1, sds.shape, sds.dtype)
        return jax.tree_util.tree_map_with_path(fill, shapes)
    return jax.jit(build)(base_key(seed))


def leaf_names(shapes):
    """[(name, layer or -1, shape)] of every weight, one per layer for
    stacked leaves, in tree order."""
    out = []
    for path, sds in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        p = _path(path)
        if p.startswith(STACK):
            out += [(p[len(STACK):], l, sds.shape[1:])
                    for l in range(sds.shape[0])]
        else:
            out.append((p, -1, sds.shape))
    return out
