"""Weights from the seed, made by the benchmark on the device.

Every weight is a pure function of (seed, leaf name, layer): 32-bit
counter-based random bits turned into a uniform value (of variance
1/fan_in for a matrix, 1 for the embedding, within 0.1 of 1 for a
norm's scale) and rounded once to the served type.  The program gets
them in its own parameter layout from one jitted call; the reference
makes the same values layer by layer, from the same names, without ever
touching what the program holds.

The rule, for any tree of the program:

- A leaf under `<stack>/stack/` (a layer stack: `stack`, `stack_dense`,
  `stack_moe`, `enc_stack`, ...) is drawn one layer at a time, at its
  per-layer shape, with the layer's index within its stack.  Its name is
  the path below `<stack>/stack/`, prefixed by `<stack>/` for every stack
  but `stack`: `stack/stack/attn/wq` is `attn/wq`, `stack_moe/stack/moe/
  w_up` is `stack_moe/moe/w_up`.  So no two layers of a model share a
  (name, layer) pair, and a reference can draw any one layer alone.
- Any other leaf is drawn whole, at layer -1, under its path.
- A matrix's fan-in is its contracted axis, `shape[-2]` of the per-layer
  shape: `d` of a `[d, F]` projection, `d` of an expert stack's
  `[E, d, F]`, `F` of its `[E, F, d]`.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


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
    """One weight array.  `layer` is -1 outside a layer stack; it may be
    a traced index."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer + 1)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5   # exact
    kind = kind_of(name)
    if kind == "scale":
        w = 1.0 + u * 0.2
    else:
        fan_in = shape[-2] if len(shape) > 1 else shape[0]
        std = 1.0 if kind == "embed" else fan_in ** -0.5
        w = u * (std * math.sqrt(12.0))
    return w.astype(dtype)


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def stacked_name(path):
    """The leaf name of a layer stack's leaf at tree `path`, or None where
    the leaf is not in a layer stack."""
    top, _, rest = _path(path).partition("/")
    if not rest.startswith("stack/"):
        return None
    rest = rest[len("stack/"):]
    return rest if top == "stack" else f"{top}/{rest}"


def make_params(shapes, seed: int):
    """The program's parameter tree (ShapeDtypeStructs in `shapes`,
    e.g. from jax.eval_shape(model.init, key)) filled from the seed in
    one jitted call, on the default device."""
    def build(key):
        def fill(path, sds):
            name = stacked_name(path)
            if name is not None:
                return jax.lax.map(
                    lambda l: leaf(key, name, l, sds.shape[1:], sds.dtype),
                    jnp.arange(sds.shape[0]))
            return leaf(key, _path(path), -1, sds.shape, sds.dtype)
        return jax.tree_util.tree_map_with_path(fill, shapes)
    return jax.jit(build)(base_key(seed))


def leaf_names(shapes):
    """[(name, layer or -1, shape)] of every weight, one per layer for
    stacked leaves, in tree order."""
    out = []
    for path, sds in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = stacked_name(path)
        if name is not None:
            out += [(name, l, sds.shape[1:]) for l in range(sds.shape[0])]
        else:
            out.append((_path(path), -1, sds.shape))
    return out
