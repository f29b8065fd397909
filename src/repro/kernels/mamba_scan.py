"""Mamba2 chunked SSD scan — Pallas TPU kernel.

The SSD (state-space dual) insight [arXiv:2405.21060]: within a chunk the
recurrence is a *masked attention-like matmul* (MXU work), across chunks it
is a tiny state recurrence (carried in VMEM scratch). The GPU version tiles
for warps/SMEM; here the chunk matmuls are shaped for the 128x128 MXU and
the [N, P] state never leaves VMEM between chunk iterations:

  grid = (B, H, L/chunk), chunk dim innermost + 'arbitrary' (sequential);
  per-iteration VMEM blocks:  dtx [T, P], ldec [T, lanes], b/c [T, N]
  scratch: h [N, P] f32 — the recurrent state, initialized at chunk 0.

Inputs are pre-arranged by ops.py into head-major layout so every BlockSpec
is a plain slice:
  dtx  [B, H, L, P]   dt-weighted inputs (dt[...,None] * x)
  ldec [B, H, L]      per-step log decay (A * dt), <= 0
  b, c [B, L, N]      shared across heads (single SSD group)
Output y [B, H, L, P]; the D*x skip connection is applied by ops.py outside.
Final state h [B, H, N, P] is a second output (needed for decode prefill).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128


def _ssd_kernel(h0_ref, dtx_ref, ldec_ref, b_ref, c_ref, y_ref, h_out_ref,
                h_ref, *, chunk: int, num_chunks: int):
    ck = pl.program_id(2)

    @pl.when(ck == 0)
    def _init():
        # resume from the caller's carried state (in-model chunked prefill:
        # each prompt chunk continues the scan where the last one stopped)
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    dtx = dtx_ref[0, 0].astype(jnp.float32)               # [T, P]
    ldec = ldec_ref[0, 0, :, 0].astype(jnp.float32)       # [T]
    b = b_ref[0].astype(jnp.float32)                      # [T, N]
    c = c_ref[0].astype(jnp.float32)                      # [T, N]

    cum = jnp.cumsum(ldec)                                # inclusive [T]
    # intra-chunk: masked (C B^T ⊙ decay) @ dtx
    seg = cum[:, None] - cum[None, :]                     # [T, T]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(cols <= rows, jnp.exp(seg), 0.0)
    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [T, T]
    y_intra = jax.lax.dot_general(g * m, dtx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y += exp(cum) * (C @ h_prev)
    h_prev = h_ref[...]                                   # [N, P]
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(cum[-1]) h_prev + B^T @ (w ⊙ dtx)
    w = jnp.exp(cum[-1] - cum)                            # [T]
    s_in = jax.lax.dot_general(b, w[:, None] * dtx,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [N, P]
    h_ref[...] = jnp.exp(cum[-1]) * h_prev + s_in

    @pl.when(ck == num_chunks - 1)
    def _emit_state():
        h_out_ref[0, 0] = h_ref[...].astype(h_out_ref.dtype)


def ssd_scan(dtx: jax.Array, ldec: jax.Array, b: jax.Array, c: jax.Array, *,
             chunk: int = 128, h0: jax.Array = None, interpret: bool = False):
    """dtx: [B, H, L, P]; ldec: [B, H, L]; b, c: [B, L, N];
    h0: [B, H, N, P] initial state (None = zeros — fresh sequence).

    Returns (y [B, H, L, P], h_final [B, H, N, P])."""
    B, H, L, P = dtx.shape
    N = b.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    # lane-shape the per-step decay for TPU tiling: [B, H, L, 1]
    ldec4 = ldec[..., None]
    if h0 is None:
        h0 = jnp.zeros((B, H, N, P), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, N, P), lambda bb, hh, ck: (bb, hh, 0, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda bb, hh, ck: (bb, hh, ck, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bb, hh, ck: (bb, hh, ck, 0)),
            pl.BlockSpec((1, chunk, N), lambda bb, hh, ck: (bb, ck, 0)),
            pl.BlockSpec((1, chunk, N), lambda bb, hh, ck: (bb, ck, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bb, hh, ck: (bb, hh, ck, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bb, hh, ck: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), dtx.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="xfa_ssd_scan",
    )(h0.astype(jnp.float32), dtx, ldec4, b, c)
    return y, h
