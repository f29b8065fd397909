"""Blockwise causal GQA flash attention — Pallas TPU kernel.

TPU adaptation of FlashAttention [arXiv:2205.14135 / 2307.08691]: instead of
a CUDA thread-block tiling we tile for the MXU/VMEM hierarchy —

  grid = (B, Hq, Sq/BQ, Sk/BK), kv-block dim innermost and 'arbitrary'
  (sequential) so the online-softmax accumulators live in VMEM scratch across
  kv iterations; batch/head/q-block dims are 'parallel'.

  q block   [BQ, D]  VMEM   (revisited for every kv block — Mosaic pipelines)
  k,v block [BK, D]  VMEM   (GQA: index_map folds q-head -> kv-head, so MQA
                             kv=1 never replicates KV into VMEM)
  acc       [BQ, D]  f32 scratch; m, l [BQ, 128] f32 scratch (TPU wants the
                             minor dim lane-shaped; col 0 is the live value)

Causal skipping: kv blocks strictly above the diagonal contribute nothing;
`pl.when` skips their FLOPs (the grid itself is not pruned — Mosaic requires
a static grid; the skipped iterations cost only the (tiny) bounds check).

Block sizes default to 128x128: the MXU is 128x128 and the f32 VMEM working
set (BQ*D acc + 2*BK*D kv + BQ*BK scores) stays < 1 MB for D<=256.

Backward: `flash_attention` is a `jax.custom_vjp`.  Its forward is this
kernel, which then also writes the per-row log-sum-exp it already holds
in scratch (m + log l, lane-broadcast [B, Hq, Sq, 128] like m/l, sliced
to [B, Hq, Sq] outside).  Its backward is the blockwise-jnp
FlashAttention-2 backward `ref.flash_attention_bwd`: it saves only
(q, k, v, o, lse) and recomputes p one kv block at a time, never
materializing [Sq, Sk].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref


NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, *refs, sm_scale: float, causal: bool,
                  block_q: int, block_k: int, logit_softcap: float,
                  num_kv_blocks: int, with_lse: bool):
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block strictly above the diagonal -> no contribution
    run = jnp.bool_(True) if not causal else (
        ik * block_k <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)                 # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                 # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_ref[:, 0]                                # [BQ]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                     # [BQ, BK]
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        # guard fully-masked rows (can only happen with q_offset padding)
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = m_ref[...] + jnp.log(
                jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...]))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    logit_softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D]; returns [B, Hq, Sq, D].
    Differentiable: see the module docstring for the backward."""
    D = q.shape[-1]
    block_q = min(block_q, q.shape[2])
    block_k = min(block_k, k.shape[2])
    scale = sm_scale if sm_scale is not None else D ** -0.5
    return _flash(q, k, v, causal, scale, logit_softcap, block_q, block_k,
                  interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, logit_softcap, block_q, block_k,
           interpret):
    return _flash_call(q, k, v, causal, scale, logit_softcap, block_q,
                       block_k, interpret, with_lse=False)


def _flash_fwd(q, k, v, causal, scale, logit_softcap, block_q, block_k,
               interpret):
    o, lse = _flash_call(q, k, v, causal, scale, logit_softcap, block_q,
                         block_k, interpret, with_lse=True)
    return o, (q, k, v, o, lse[..., 0])


def _flash_bwd(causal, scale, logit_softcap, block_q, block_k, interpret,
               res, do):
    # the largest kv block up to 512 that tiles Sk: fewer scan steps than
    # the forward's 128, same O(Sq * block) live scores
    bk = math.gcd(res[1].shape[2], 512)
    return ref.flash_attention_bwd(res, do, causal=causal, sm_scale=scale,
                                   block_k=bk, logit_softcap=logit_softcap)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_call(q, k, v, causal, scale, logit_softcap, block_q, block_k,
                interpret, *, with_lse: bool):
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, logit_softcap=logit_softcap, num_kv_blocks=nk,
        with_lse=with_lse)

    q_block = lambda b, h, iq, ik: (b, h, iq, 0)
    out_specs = pl.BlockSpec((1, 1, block_q, D), q_block)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if with_lse:
        out_specs = [out_specs, pl.BlockSpec((1, 1, block_q, LANES), q_block)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, Hq, Sq, LANES), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_block),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik: (b, h // g, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="xfa_flash_attention",
    )(q, k, v)
