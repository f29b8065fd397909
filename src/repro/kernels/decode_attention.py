"""Flash-decode (split-K) + positioned-chunk attention — Pallas TPU kernels.

Decode attention is memory-bound: one query row vs a [S, D] KV cache. The
kernel streams KV blocks through VMEM with the online-softmax carried in
scratch (grid kv dim 'arbitrary'), never materializing the [S] score row in
HBM. The q "row" is padded to 8 sublanes to satisfy TPU tiling; all q-heads
of one kv-head are processed together so GQA reuses each KV block g times
from VMEM (arithmetic intensity ×g).

Distributed split-K happens ABOVE the kernel: parallel/context.py shards S
across the mesh, each shard runs this kernel with return-style (o, m, l)
residuals computed from its local range, and the partials merge with
ref.combine_decode_partials after one small all-gather.

`chunk_attention` generalizes the same streaming structure from one query
row to a T-token chunk at per-row cache offsets (in-model chunked prefill):
the mask becomes OFFSET-CAUSAL — query t of batch row b sees cache columns
<= pos[b] + t — and the per-row early exit skips KV blocks past
pos[b] + T, so a slot resuming at depth 40 never streams its neighbour's
32k-deep cache.  T == 1 with kv_len = pos + 1 is exactly decode attention.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *,
                   sm_scale: float, block_k: int, num_kv_blocks: int,
                   with_residuals: bool):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[pl.program_id(0)]  # scalar-prefetched row length

    # per-row early exit: this row is done once ik*block_k passes ITS
    # length — other rows of the same call keep streaming their blocks
    @pl.when(ik * block_k < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, BK]
        cols = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)
        if with_residuals:
            m_out_ref[0, 0] = m_ref[...].astype(m_out_ref.dtype)
            l_out_ref[0, 0] = l_ref[...].astype(l_out_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: Optional[jax.Array] = None,
                     sm_scale: Optional[float] = None,
                     block_k: int = 512, interpret: bool = False,
                     return_residuals: bool = False):
    """q: [B, Hq, D]; k, v: [B, Hkv, S, D] -> [B, Hq, D].

    kv_len: [B] int32 PER-ROW valid lengths (None = full S).  Under
    continuous batching every serving slot decodes at its own depth, so
    rows of one call carry arbitrary mixed lengths: the kernel reads each
    row's length from SMEM (scalar prefetch), skips whole KV blocks past
    it (`pl.when` on the arbitrary grid dim — a row at depth 100 does not
    pay for a neighbour at 32k), and masks the partial block with a per-column
    iota compare.  A fully-masked row (kv_len == 0, e.g. an empty pool
    slot) short-circuits every block; the l == 0 guard in _finalize
    yields zeros instead of 0/0 NaNs.  return_residuals=True additionally
    returns (m, l): [B, Hq] for distributed split-K merge."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if kv_len is None:
        kv_len = jnp.full((B,), S, jnp.int32)

    # group q heads by kv head: [B, Hkv, G, D]
    qg = q.reshape(B, Hkv, g, D)

    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, block_k=block_k, num_kv_blocks=nk,
        with_residuals=return_residuals)

    out_shapes = [
        jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        jax.ShapeDtypeStruct((B, Hkv, g, LANES), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, g, LANES), jnp.float32),
    ]
    # per-row lengths ride as a scalar-prefetch operand (resident in SMEM
    # before the body runs), exactly as the paged kernels pass theirs
    row = lambda b, h, ik, len_ref: (b, h, 0, 0)
    kv_block = lambda b, h, ik, len_ref: (b, h, ik, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, D), row),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, D), row),
            pl.BlockSpec((1, 1, g, LANES), row),
            pl.BlockSpec((1, 1, g, LANES), row),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="xfa_decode_attention",
    )(jnp.asarray(kv_len, jnp.int32), qg, k, v)

    o = o.reshape(B, Hq, D)
    if return_residuals:
        return o, (m[..., 0].reshape(B, Hq), l[..., 0].reshape(B, Hq))
    return o


def _row_block(rows: int, target: int = 512) -> int:
    """Query rows per grid step of the chunk kernels: all of them up to
    `target`, else the largest power-of-two divisor up to it.  The
    scratch and the [rows, kv block] scores stay inside scoped VMEM for
    any chunk width (a whole 512-token chunk of 8 q heads per kv head
    is 4096 rows, 17.6 MiB of scoped VMEM against a 16 MiB limit)."""
    if rows <= target:
        return rows
    br = math.gcd(rows, target)
    return br if br % 8 == 0 else rows      # (8, 128) tiling or one block


def _chunk_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, sm_scale: float, block_k: int, num_kv_blocks: int,
                  chunk: int, block_rows: int):
    """Offset-causal flash over the cache for one (batch, kv-head, row
    block) triple.

    The kv head's query rows are [G*T, D] — all q heads of the kv head ×
    the whole chunk — laid out (g, t) row-major so row r's query index is
    r % T; its column limit is pos + r % T (the row's own absolute
    position).  The grid walks them block_rows at a time."""
    row0 = pl.program_id(2) * block_rows
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[pl.program_id(0)]  # scalar-prefetched row offset

    # per-row early exit: no query of this chunk reaches past pos + T - 1
    @pl.when(ik * block_k < pos + chunk)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [BR, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos + rows % chunk, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def _decode_paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *,
                         sm_scale: float, page_size: int, num_pages: int):
    """Paged flash-decode body: identical online softmax to _decode_kernel,
    but the KV grid dimension walks BLOCK-TABLE SLOTS — the BlockSpec
    index map already dereferenced bt_ref[b, ik] (scalar prefetch), so
    k_ref/v_ref hold page `block_table[b, ik]` of the arena.  Ungranted
    slots point at the reserved scratch page 0; the kv_len column mask
    gives those columns exactly-zero softmax mass."""
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]

    @pl.when(ik * page_size < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        cols = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_pages - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, *, block_table: jax.Array,
                           kv_len: jax.Array,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: [B, Hq, D]; k_pages, v_pages: [P, Hkv, page_size, D] arena;
    block_table: [B, NB] int32 page ids; kv_len: [B] -> [B, Hq, D].

    The block table and per-row lengths ride as SCALAR-PREFETCH operands
    (pltpu.PrefetchScalarGridSpec): they are resident before the body
    runs, so the k/v BlockSpec index maps dereference bt_ref[b, ik] to
    DMA exactly the page each (row, kv-slot) grid point needs — the
    kernel streams a slot's own pages and nothing else, and a row at
    depth 100 never touches a neighbour's 32k-deep allocation.  The
    per-row early exit additionally skips whole slots past kv_len (the
    scratch-page fetch for those slots is dead DMA, never compute)."""
    B, Hq, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    NB = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5

    qg = q.reshape(B, Hkv, g, D)
    kernel = functools.partial(
        _decode_paged_kernel, sm_scale=scale, page_size=ps, num_pages=NB)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, NB),
        in_specs=[
            pl.BlockSpec((1, 1, g, D),
                         lambda b, h, ik, len_ref, bt_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda b, h, ik, len_ref, bt_ref:
                         (bt_ref[b, ik], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, D),
                         lambda b, h, ik, len_ref, bt_ref:
                         (bt_ref[b, ik], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, D), lambda b, h, ik, len_ref, bt_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="xfa_decode_attention_paged",
    )(jnp.asarray(kv_len, jnp.int32), jnp.asarray(block_table, jnp.int32),
      qg, k_pages, v_pages)
    return o.reshape(B, Hq, D)


def _chunk_paged_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *,
                        sm_scale: float, page_size: int, num_pages: int,
                        chunk: int, block_rows: int):
    """Paged offset-causal chunk body (see _chunk_kernel): q rows are
    (g, t) row-major in blocks of block_rows, column limit
    pos + r % chunk; the KV grid walks block-table slots with the page id
    prefetched into the BlockSpec."""
    b = pl.program_id(0)
    row0 = pl.program_id(2) * block_rows
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]

    # per-row early exit: no query of this chunk reaches past pos + T - 1
    @pl.when(ik * page_size < pos + chunk)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale       # [BR, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [PS, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ik * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols <= pos + rows % chunk, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == num_pages - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l[:, None]).astype(o_ref.dtype)


def chunk_attention_paged(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, *, block_table: jax.Array,
                          pos: jax.Array, sm_scale: Optional[float] = None,
                          interpret: bool = False) -> jax.Array:
    """q: [B, Hq, T, D] chunk queries; k_pages, v_pages:
    [P, Hkv, page_size, D] arena; block_table: [B, NB]; pos: [B]
    -> [B, Hq, T, D].

    The paged generalization of chunk_attention: the chunk's own K/V was
    already scattered through the block table at virtual rows
    [pos, pos+T), and query t of row b attends virtual columns
    <= pos[b] + t.  Block-table slots are this kernel's KV blocks —
    slots past a row's pos + T early-exit exactly like dense KV blocks
    do, so the mixed-depth serving property is preserved page-granular."""
    B, Hq, T, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    NB = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5

    qg = q.reshape(B, Hkv, g, T, D).reshape(B, Hkv, g * T, D)
    br = _row_block(g * T)
    kernel = functools.partial(
        _chunk_paged_kernel, sm_scale=scale, page_size=ps, num_pages=NB,
        chunk=T, block_rows=br)

    rows = lambda b, h, iq, ik, pos_ref, bt_ref: (b, h, iq, 0)
    page = lambda b, h, iq, ik, pos_ref, bt_ref: (bt_ref[b, ik], h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, g * T // br, NB),
        in_specs=[
            pl.BlockSpec((1, 1, br, D), rows),
            pl.BlockSpec((1, 1, ps, D), page),
            pl.BlockSpec((1, 1, ps, D), page),
        ],
        out_specs=pl.BlockSpec((1, 1, br, D), rows),
        scratch_shapes=[
            pltpu.VMEM((br, D), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g * T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="xfa_chunk_attention_paged",
    )(jnp.asarray(pos, jnp.int32), jnp.asarray(block_table, jnp.int32),
      qg, k_pages, v_pages)
    return o.reshape(B, Hq, T, D)


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    pos: jax.Array, sm_scale: Optional[float] = None,
                    block_k: int = 512, interpret: bool = False):
    """q: [B, Hq, T, D] chunk queries; k, v: [B, Hkv, S, D] full cache;
    pos: [B] int32 per-row offsets -> [B, Hq, T, D].

    Query t of row b attends cache columns <= pos[b] + t — the
    offset-causal mask of in-model chunked prefill: the chunk's own K/V
    was just scattered at [pos, pos+T) and everything before pos is prior
    cache content, so one compiled call serves serving slots resuming
    their prompts at arbitrary mixed depths."""
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    scale = sm_scale if sm_scale is not None else D ** -0.5
    pos = jnp.asarray(pos, jnp.int32)

    # group q heads by kv head and flatten (g, T) into kernel rows
    qg = q.reshape(B, Hkv, g, T, D).reshape(B, Hkv, g * T, D)

    br = _row_block(g * T)
    kernel = functools.partial(
        _chunk_kernel, sm_scale=scale, block_k=block_k, num_kv_blocks=nk,
        chunk=T, block_rows=br)

    # per-row offsets ride as a scalar-prefetch operand (see decode)
    rows = lambda b, h, iq, ik, pos_ref: (b, h, iq, 0)
    kv_block = lambda b, h, iq, ik, pos_ref: (b, h, ik, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, g * T // br, nk),
        in_specs=[
            pl.BlockSpec((1, 1, br, D), rows),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
            pl.BlockSpec((1, 1, block_k, D), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, br, D), rows),
        scratch_shapes=[
            pltpu.VMEM((br, D), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
            pltpu.VMEM((br, LANES), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g * T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="xfa_chunk_attention",
    )(pos, qg, k, v)

    return o.reshape(B, Hq, T, D)
