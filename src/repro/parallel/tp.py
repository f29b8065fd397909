"""Manual Megatron-style tensor-parallel linear pairs (shard_map).

WHY (EXPERIMENTS.md §Perf, granite multi-pod): under plain pjit, the
backward dx of every TP linear is an all-reduce of the F32-ACCUMULATED
transpose-dot output — GSPMD places the AR before the bf16 downcast, and
emits one AR per projection. 10.9 TB/step on granite-20b train (2x16x16).

These layers take control of exactly those collectives:

  col_row_mlp:   up/gate column-parallel (no fwd comm) -> local activation
                 -> down row-parallel (ONE fwd psum, bf16). Backward: dx of
                 the whole block is ONE bf16 psum (the up/gate dx partials
                 are summed LOCALLY before reducing); dw stay local partials
                 reduced over the batch axes in f32 (numerics preserved
                 where it matters — weight grads).

Forward/backward numerics vs the pjit path: identical contraction order in
f32 accumulation; only the dx cotangent crossing the block boundary is
rounded to bf16 (standard mixed-precision practice). Equivalence-tested in
tests/test_tp_linear.py; enabled per-model with ModelConfig.manual_tp.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.axes import get_rules, get_runtime_mesh
from jax import shard_map


def _axes(mesh: Mesh) -> Tuple[Tuple[str, ...], Optional[str]]:
    rules = get_rules()
    batch = tuple(a for a in rules.get("batch", ("pod", "data"))
                  if a in mesh.axis_names)
    model = next((a for a in rules.get("model", ("model",))
                  if a in mesh.axis_names), None)
    return batch, model


def manual_tp_available(d_ff: int) -> bool:
    mesh = get_runtime_mesh()
    if mesh is None:
        return False
    batch, model = _axes(mesh)
    if model is None:
        return False
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))[model]
    return msize > 1 and d_ff % msize == 0


def col_row_mlp(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
                w_gate: Optional[jax.Array], gated: bool) -> jax.Array:
    """x: [B, S, d] (batch-sharded, feature-replicated); w_up/w_gate:
    [d, f] column-sharded; w_down: [f, d] row-sharded. Returns [B, S, d]."""
    mesh = get_runtime_mesh()
    batch, model = _axes(mesh)
    bspec = P(batch, None, None)
    ws_in = (P(None, model), P(model, None)) + \
        ((P(None, model),) if gated else ())

    def body(x_l, w_up_l, w_down_l, *maybe_gate):
        return _mlp_core(x_l, w_up_l, w_down_l,
                         maybe_gate[0] if maybe_gate else None,
                         gated, model, batch)

    fn = shard_map(body, mesh=mesh,
                       in_specs=(bspec,) + ws_in, out_specs=bspec,
                       check_vma=False)
    args = (x, w_up, w_down) + ((w_gate,) if gated else ())
    return fn(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _mlp_core(x_l, w_up_l, w_down_l, w_gate_l, gated, model_axis,
              batch_axes):
    y, _ = _mlp_fwd(x_l, w_up_l, w_down_l, w_gate_l, gated, model_axis,
                    batch_axes)
    return y


def _act(h_up, h_gate, gated):
    if gated:
        return (jax.nn.silu(h_gate.astype(jnp.float32))
                * h_up.astype(jnp.float32)).astype(h_up.dtype)
    return jax.nn.gelu(h_up.astype(jnp.float32)).astype(h_up.dtype)


def _mlp_fwd(x_l, w_up_l, w_down_l, w_gate_l, gated, model_axis,
             batch_axes):
    h_up = jnp.einsum("bsd,df->bsf", x_l, w_up_l.astype(x_l.dtype))
    h_gate = (jnp.einsum("bsd,df->bsf", x_l, w_gate_l.astype(x_l.dtype))
              if gated else None)
    h = _act(h_up, h_gate, gated)
    y_part = jnp.einsum("bsf,fd->bsd", h, w_down_l.astype(x_l.dtype))
    with jax.named_scope("mlp_fwd_psum"):
        y = jax.lax.psum(y_part, model_axis)      # ONE bf16 psum forward
    return y, (x_l, w_up_l, w_down_l, w_gate_l, h_up, h_gate)


def _psum_batch(v, batch_axes):
    for ax in batch_axes:
        v = jax.lax.psum(v, ax)
    return v


def _mlp_bwd(gated, model_axis, batch_axes, res, dy):
    x_l, w_up_l, w_down_l, w_gate_l, h_up, h_gate = res
    dy = dy.astype(x_l.dtype)                     # bf16 cotangent
    h = _act(h_up, h_gate, gated)
    # dw: f32 accumulation + explicit psum over the batch axes (check_vma is
    # off, so replicated-input cotangents must be reduced by hand)
    dw_down = _psum_batch(
        jnp.einsum("bsf,bsd->fd", h, dy,
                   preferred_element_type=jnp.float32), batch_axes)
    dh = jnp.einsum("bsd,fd->bsf", dy, w_down_l.astype(dy.dtype))
    # activation backward in f32
    dhf = dh.astype(jnp.float32)
    if gated:
        sg = jax.nn.sigmoid(h_gate.astype(jnp.float32))
        silu = h_gate.astype(jnp.float32) * sg
        d_up = (dhf * silu)
        d_gate = dhf * h_up.astype(jnp.float32) * sg \
            * (1 + h_gate.astype(jnp.float32) * (1 - sg))
    else:
        _, gelu_vjp = jax.vjp(
            lambda t: jax.nn.gelu(t.astype(jnp.float32)), h_up)
        (d_up,) = gelu_vjp(dhf)
        d_up = d_up.astype(jnp.float32)
        d_gate = None
    d_up = d_up.astype(x_l.dtype)
    dw_up = _psum_batch(
        jnp.einsum("bsd,bsf->df", x_l, d_up,
                   preferred_element_type=jnp.float32), batch_axes)
    dx_part = jnp.einsum("bsf,df->bsd", d_up, w_up_l.astype(x_l.dtype))
    dw_gate = None
    if gated:
        d_gate = d_gate.astype(x_l.dtype)
        dw_gate = _psum_batch(
            jnp.einsum("bsd,bsf->df", x_l, d_gate,
                       preferred_element_type=jnp.float32), batch_axes)
        # sum the up/gate dx partials LOCALLY before the single psum
        dx_part = dx_part + jnp.einsum("bsf,df->bsd", d_gate,
                                       w_gate_l.astype(x_l.dtype))
    with jax.named_scope("mlp_bwd_psum"):
        dx = jax.lax.psum(dx_part, model_axis)    # ONE bf16 psum backward
    dw_up = dw_up.astype(w_up_l.dtype)
    dw_down = dw_down.astype(w_down_l.dtype)
    if dw_gate is not None:
        dw_gate = dw_gate.astype(w_gate_l.dtype)
    return dx, dw_up, dw_down, dw_gate


_mlp_core.defvjp(_mlp_fwd, _mlp_bwd)
