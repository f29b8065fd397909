"""Fused RMSNorm — Pallas TPU kernel.

Fuses the square-reduce, rsqrt, scale and (optional) residual-add into one
VMEM pass over [BR, D] row blocks: 1 HBM read + 1 write instead of the 3-4
passes an unfused chain costs (norm is memory-bound; the fusion matters for
the memory roofline term). Reduction runs in f32 regardless of io dtype.

Backward: `rmsnorm` and `rmsnorm_add` are `jax.custom_vjp`s whose forward
is the kernel and whose backward is the closed-form RMSNorm gradient in
plain jnp (`_norm_grads`, f32): it saves only the norm's input and the
weight and recomputes the rsqrt, one elementwise pass plus two row
reductions that XLA fuses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # [BR, D]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_add_kernel(x_ref, r_ref, w_ref, o_ref, res_ref, *, eps: float):
    s = (x_ref[...].astype(jnp.float32)
         + r_ref[...].astype(jnp.float32))                # fused residual add
    res_ref[...] = s.astype(res_ref.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _norm_grads(s, w, g, eps: float):
    """Cotangents of y = rmsnorm(s) * w: (ds, dw) for y's cotangent g."""
    sf = s.astype(jnp.float32)
    gw = g.astype(jnp.float32) * w.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(sf * sf, axis=-1, keepdims=True) + eps)
    ds = r * (gw - sf * (r * r) * jnp.mean(gw * sf, axis=-1, keepdims=True))
    dw = jnp.sum((g.astype(jnp.float32) * sf * r).reshape(-1, s.shape[-1]),
                 axis=0)
    return ds, dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: [..., D]; w: [D]."""
    return _rmsnorm_call(x, w, eps, block_rows, interpret)


def _rmsnorm_fwd(x, w, eps, block_rows, interpret):
    return _rmsnorm_call(x, w, eps, block_rows, interpret), (x, w)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    x, w = res
    dx, dw = _norm_grads(x, w, g, eps)
    return dx.astype(x.dtype), dw


rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def _rmsnorm_call(x, w, eps, block_rows, interpret):
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    # pad rows to a multiple of the block
    pad = (-R) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    nr = x2.shape[0] // br

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="xfa_rmsnorm",
    )(x2, w)
    if pad:
        out = out[:R]
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rmsnorm_add(x: jax.Array, residual: jax.Array, w: jax.Array,
                eps: float = 1e-5, block_rows: int = 256,
                interpret: bool = False):
    """Fused (x + residual) -> (rmsnorm(sum), sum). Saves one HBM round-trip
    in the pre-norm transformer block pattern."""
    return _rmsnorm_add_call(x, residual, w, eps, block_rows, interpret)


def _rmsnorm_add_fwd(x, residual, w, eps, block_rows, interpret):
    y, s = _rmsnorm_add_call(x, residual, w, eps, block_rows, interpret)
    return (y, s), (s, w)


def _rmsnorm_add_bwd(eps, block_rows, interpret, res, g):
    s, w = res
    gy, gs = g
    ds, dw = _norm_grads(s, w, gy, eps)
    ds = ds + gs.astype(jnp.float32)          # s is also an output
    return ds.astype(s.dtype), ds.astype(s.dtype), dw


rmsnorm_add.defvjp(_rmsnorm_add_fwd, _rmsnorm_add_bwd)


def _rmsnorm_add_call(x, residual, w, eps, block_rows, interpret):
    # the backward returns both input cotangents in the sum's dtype
    assert x.dtype == residual.dtype, (x.dtype, residual.dtype)
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    r2 = residual.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        r2 = jnp.pad(r2, ((0, pad), (0, 0)))
    nr = x2.shape[0] // br

    y, s = pl.pallas_call(
        functools.partial(_rmsnorm_add_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((br, D), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
            jax.ShapeDtypeStruct(x2.shape, x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="xfa_rmsnorm_add",
    )(x2, r2, w)
    if pad:
        y, s = y[:R], s[:R]
    return y.reshape(orig_shape), s.reshape(orig_shape)
