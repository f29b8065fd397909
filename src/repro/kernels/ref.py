"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes in interpret mode) AND the
CPU-executable implementation the models fall back to when no TPU is present
(ops.py `impl='auto'`). They favour clarity over speed; the `*_chunked`
variants mirror the kernels' blocking algebra and are themselves validated
against the naive forms.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ------------------------------------------------------------ attention ----
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: Optional[float] = None,
              logit_softcap: float = 0.0,
              q_offset: int = 0) -> jax.Array:
    """Reference GQA attention.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D]; Hq % Hkv == 0.
    q_offset: absolute position of q[0] (for decode: Sk - Sq).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, Hkv, g, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    if logit_softcap > 0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if causal:
        rows = jnp.arange(Sq)[:, None] + q_offset
        cols = jnp.arange(Sk)[None, :]
        s = jnp.where(cols <= rows, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, sm_scale: Optional[float] = None,
                      logit_softcap: float = 0.0, block_k: int = 1024,
                      q_offset: int = 0) -> jax.Array:
    """Flash-pattern attention in pure jnp: online softmax over KV blocks via
    lax.scan, never materializing the [Sq, Sk] score matrix — with a FLASH
    BACKWARD (custom_vjp below) that saves only (q, k, v, o, lse) and
    recomputes p per block, exactly like the FlashAttention-2 backward.
    Without it, jax AD stacks the per-block p residuals: +1 GiB/layer on
    tinyllama train_4k (measured — EXPERIMENTS.md §Perf iteration 0).

    This is the dry-run stand-in for the Pallas kernel (Mosaic cannot lower
    on the CPU backend): identical FLOPs and O(Sq·block_k) live memory, so
    memory_analysis() reflects the fused-kernel footprint. Causal blocks
    above the diagonal are masked, not skipped (a static scan) — the compute
    roofline term therefore upper-bounds the kernel, which does skip them;
    EXPERIMENTS.md §Roofline notes the ≤2x causal adjustment.
    """
    # Head padding for TP: when Hq does not divide the model axis (qwen3 40,
    # starcoder2 36, internvl 14 vs TP=16), SPMD falls back to factorized
    # head shardings and re-gathers K/V blocks EVERY chunk iteration
    # (measured 1.5 TB/step on qwen3 prefill_32k — EXPERIMENTS.md §Perf).
    # Padding to the next multiple costs <=20% attention FLOPs and keeps
    # every tensor cleanly head-sharded; padded heads are sliced off (and
    # autodiff slices their cotangents to zero).
    from repro.parallel.axes import axis_size
    msize = axis_size("model")
    Hq = q.shape[1]
    Hkv = k.shape[1]
    pad_h = (-Hq) % msize if msize > 1 else 0
    if pad_h:
        # repeat kv heads FIRST (AD of repeat folds dk/dv back), then pad
        # all three uniformly — keeps GQA group alignment for any g
        g = Hq // Hkv
        kr = k if g == 1 else jnp.repeat(k, g, axis=1)
        vr = v if g == 1 else jnp.repeat(v, g, axis=1)
        padded = [jnp.pad(t, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
                  for t in (q, kr, vr)]
        o = attention_chunked(*padded, causal=causal, sm_scale=sm_scale,
                              logit_softcap=logit_softcap,
                              block_k=block_k, q_offset=q_offset)
        return o[:, :Hq]
    if logit_softcap == 0.0:
        scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
        return _flash_chunked(q, k, v, causal, scale, block_k, q_offset)
    return _attention_chunked_impl(q, k, v, causal=causal, sm_scale=sm_scale,
                                   logit_softcap=logit_softcap,
                                   block_k=block_k, q_offset=q_offset)


def _attention_chunked_impl(q, k, v, *, causal, sm_scale, logit_softcap,
                            block_k, q_offset, return_lse: bool = False):
    from repro.parallel.axes import shard_dims  # local: avoid import cycle
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    block_k = min(block_k, Sk)
    assert Sk % block_k == 0, (Sk, block_k)
    nk = Sk // block_k
    scale = sm_scale if sm_scale is not None else D ** -0.5
    # GQA by kv-head repeat (Megatron TP>kv pattern): every tensor stays 4D
    # [B, Hq, ...] so 'model' shards the q-head dim wherever divisible. The
    # repeat is free per-device under head sharding (each rank gathers only
    # the kv heads its q heads need).
    _c = lambda t: shard_dims(t, {0: "batch", 1: "model"})
    qf = _c(q.astype(jnp.float32) * scale)
    kr = k if g == 1 else jnp.repeat(k, g, axis=1)
    vr = v if g == 1 else jnp.repeat(v, g, axis=1)
    kb = _c(kr.reshape(B, Hq, nk, block_k, D))
    vb = _c(vr.reshape(B, Hq, nk, block_k, D))
    rows = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry
        kk, vv, ik = inp
        # pin the scan carries: unconstrained while-loop carries fall back
        # to replicated under SPMD -> per-iteration all-gathers
        m, l, acc = _c(m), _c(l), _c(acc)
        kk, vv = _c(kk), _c(vv)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kk.astype(jnp.float32))
        if logit_softcap > 0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if causal:
            cols = ik * block_k + jnp.arange(block_k)
            s = jnp.where(cols[None, None, None, :]
                          <= rows[None, None, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
        return (_c(m_new), _c(l), _c(acc)), None

    m0 = _c(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32))
    l0 = _c(jnp.zeros((B, Hq, Sq), jnp.float32))
    a0 = _c(jnp.zeros((B, Hq, Sq, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    o = acc / l[..., None]
    if return_lse:
        return o.astype(q.dtype), m + jnp.log(l)
    return o.astype(q.dtype)


# ---- flash backward: save (q, k, v, o, lse); recompute p per kv block ----
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_chunked(q, k, v, causal, sm_scale, block_k, q_offset):
    return _attention_chunked_impl(q, k, v, causal=causal, sm_scale=sm_scale,
                                   logit_softcap=0.0, block_k=block_k,
                                   q_offset=q_offset)


def _flash_chunked_fwd(q, k, v, causal, sm_scale, block_k, q_offset):
    o, lse = _attention_chunked_impl(q, k, v, causal=causal,
                                     sm_scale=sm_scale, logit_softcap=0.0,
                                     block_k=block_k, q_offset=q_offset,
                                     return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_chunked_bwd(causal, sm_scale, block_k, q_offset, res, do):
    return flash_attention_bwd(res, do, causal=causal, sm_scale=sm_scale,
                               block_k=block_k, q_offset=q_offset)


def flash_attention_bwd(res, do, *, causal: bool, sm_scale: float,
                        block_k: int, q_offset: int = 0,
                        logit_softcap: float = 0.0):
    """FlashAttention-2 backward in blockwise jnp: res = (q, k, v, o, lse)
    with lse [B, Hq, Sq] the forward's log-sum-exp; p is recomputed one
    kv block at a time, so no [Sq, Sk] matrix is ever live.  Shared by
    `_flash_chunked` and the Pallas flash kernel's custom_vjp.  block_k
    must divide Sk.  Returns (dq, dk, dv)."""
    # custom_vjp bwd is traced OUTSIDE the model's named_scope — re-enter it
    # so the XFA static layer attributes these loops to the kernel scope
    with jax.named_scope("attention"):
        return _flash_bwd_impl(causal, sm_scale, block_k, q_offset,
                               logit_softcap, res, do)


def _flash_bwd_impl(causal, sm_scale, block_k, q_offset, logit_softcap,
                    res, do):
    from repro.parallel.axes import shard_dims  # local: avoid import cycle
    q, k, v, o, lse = res
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    g = Hq // Hkv
    bk = min(block_k, Sk)
    nk = Sk // bk
    _c = lambda t: shard_dims(t, {0: "batch", 1: "model"})
    qs = _c(q.astype(jnp.float32) * sm_scale)
    dof = _c(do.astype(jnp.float32))
    lse_r = _c(lse.astype(jnp.float32))
    # delta_i = rowsum(dO ∘ O)
    delta = _c(jnp.sum(dof * o.astype(jnp.float32), axis=-1))
    kr = k if g == 1 else jnp.repeat(k, g, axis=1)
    vr = v if g == 1 else jnp.repeat(v, g, axis=1)
    kb = _c(kr.reshape(B, Hq, nk, bk, D))
    vb = _c(vr.reshape(B, Hq, nk, bk, D))
    rows = jnp.arange(Sq) + q_offset

    def body(dq_acc, inp):
        kk, vv, ik = inp
        dq_acc = _c(dq_acc)
        kk, vv = _c(kk), _c(vv)
        kf, vf = kk.astype(jnp.float32), vv.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, kf)
        if logit_softcap > 0:
            t = jnp.tanh(s / logit_softcap)
            s = logit_softcap * t
        p = jnp.exp(s - lse_r[..., None])                # softmax via lse
        if causal:
            cols = ik * bk + jnp.arange(bk)
            p = jnp.where(cols[None, None, None, :]
                          <= rows[None, None, :, None], p, 0.0)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
        ds = p * (dp - delta[..., None])
        if logit_softcap > 0:
            ds = ds * (1.0 - t * t)                      # d softcap / ds
        dq_acc = _c(dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kf))
        dk = _c(jnp.einsum("bhqk,bhqd->bhkd", ds, qs))
        return dq_acc, (dk, dv)

    dq0 = _c(jnp.zeros((B, Hq, Sq, D), jnp.float32))
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0,
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), jnp.arange(nk)))
    dq = (dq * sm_scale).astype(q.dtype)
    # fold repeated-head grads back onto the Hkv heads
    dk_r = jnp.moveaxis(dk_blocks, 0, 2).reshape(B, Hq, Sk, D)
    dv_r = jnp.moveaxis(dv_blocks, 0, 2).reshape(B, Hq, Sk, D)
    if g > 1:
        dk_r = dk_r.reshape(B, Hkv, g, Sk, D).sum(axis=2)
        dv_r = dv_r.reshape(B, Hkv, g, Sk, D).sum(axis=2)
    return dq, dk_r.astype(k.dtype), dv_r.astype(v.dtype)


_flash_chunked.defvjp(_flash_chunked_fwd, _flash_chunked_bwd)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     kv_len: Optional[jax.Array] = None,
                     sm_scale: Optional[float] = None,
                     return_residuals: bool = False):
    """Reference single-token decode attention.

    q: [B, Hq, D]; k, v: [B, Hkv, S, D]. kv_len: [B] valid prefix lengths
    (positions >= kv_len are masked; None = all valid). With
    return_residuals=True also returns (m, l) for cross-shard split-K
    combination (parallel/context.py)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bhsd->bhgs", qf, k.astype(jnp.float32))
    if kv_len is not None:
        mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    o_n = (o / l).reshape(B, Hq, D).astype(q.dtype)
    if return_residuals:
        return o_n, (m.reshape(B, Hq), l.reshape(B, Hq))
    return o_n


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    pos: jax.Array,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """Reference positioned-chunk attention (offset-causal vs cache).

    q: [B, Hq, T, D] — a chunk of T query tokens whose row-b absolute
    positions are pos[b] .. pos[b]+T-1; k, v: [B, Hkv, S, D] — the FULL
    cache, whose rows [pos[b], pos[b]+T) were just written with this
    chunk's K/V.  Query t of row b attends cache columns <= pos[b] + t
    (its own prefix INCLUDING existing cache content), so one call serves
    mixed-depth serving slots; T == 1 degenerates to decode attention
    with kv_len = pos + 1 and pos == 0, T == S to plain causal prefill.
    Columns past each query's limit get exactly-zero softmax mass, so
    stale cache content beyond a row's frontier can never leak in.
    """
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, T, D)
    s = jnp.einsum("bhgtd,bhsd->bhgts", qf, k.astype(jnp.float32))
    limit = pos[:, None, None, None, None] \
        + jnp.arange(T)[None, None, None, :, None]
    cols = jnp.arange(S)[None, None, None, None, :]
    s = jnp.where(cols <= limit, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgts,bhsd->bhgtd", p, v.astype(jnp.float32))
    return (o / l).reshape(B, Hq, T, D).astype(q.dtype)


def chunk_attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            pos: jax.Array,
                            sm_scale: Optional[float] = None,
                            block_k: int = 1024) -> jax.Array:
    """Flash-pattern positioned-chunk attention in pure jnp — the dry-run
    stand-in for the Pallas chunk kernel (same semantics as
    chunk_attention, O(T·block_k) live scores instead of the [T, S]
    matrix).  Mirrors attention_chunked's SPMD discipline: q heads padded
    to the model axis, scan carries and KV blocks pinned to
    (batch, model) so the online-softmax loop never re-gathers."""
    from repro.parallel.axes import axis_size, shard_dims
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    msize = axis_size("model")
    pad_h = (-Hq) % msize if msize > 1 else 0
    if pad_h:
        kr = k if g == 1 else jnp.repeat(k, g, axis=1)
        vr = v if g == 1 else jnp.repeat(v, g, axis=1)
        padded = [jnp.pad(t, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
                  for t in (q, kr, vr)]
        return chunk_attention_blocked(*padded, pos=pos, sm_scale=sm_scale,
                                       block_k=block_k)[:, :Hq]
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    scale = sm_scale if sm_scale is not None else D ** -0.5
    _c = lambda t: shard_dims(t, {0: "batch", 1: "model"})
    qf = _c(q.astype(jnp.float32) * scale)
    kr = k if g == 1 else jnp.repeat(k, g, axis=1)
    vr = v if g == 1 else jnp.repeat(v, g, axis=1)
    kb = _c(kr.reshape(B, Hq, nk, block_k, D))
    vb = _c(vr.reshape(B, Hq, nk, block_k, D))
    limit = pos[:, None] + jnp.arange(T)[None, :]          # [B, T]

    def body(carry, inp):
        m, l, acc = carry
        kk, vv, ik = inp
        m, l, acc = _c(m), _c(l), _c(acc)
        kk, vv = _c(kk), _c(vv)
        s = jnp.einsum("bhtd,bhkd->bhtk", qf, kk.astype(jnp.float32))
        cols = ik * block_k + jnp.arange(block_k)
        s = jnp.where(cols[None, None, None, :]
                      <= limit[:, None, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bhkd->bhtd", p, vv.astype(jnp.float32))
        return (_c(m_new), _c(l), _c(acc)), None

    m0 = _c(jnp.full((B, Hq, T), NEG_INF, jnp.float32))
    l0 = _c(jnp.zeros((B, Hq, T), jnp.float32))
    a0 = _c(jnp.zeros((B, Hq, T, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


def gather_kv_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """Materialize a paged KV arena as per-row dense caches.

    pages: [P, Hkv, page_size, D] — the page arena (page 0 is the
    engine's reserved scratch page); block_table: [B, NB] int32 page ids,
    row b's virtual cache row being the concatenation of its NB pages.
    Returns [B, Hkv, NB*page_size, D].  Unassigned block-table entries
    point at page 0; whatever lives there is masked by pos/kv_len on
    every read path, so the gather never has to know the frontier."""
    g = pages[block_table]                       # [B, NB, Hkv, ps, D]
    B, NB, Hkv, ps, D = g.shape
    return jnp.moveaxis(g, 1, 2).reshape(B, Hkv, NB * ps, D)


def chunk_attention_paged(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, *, block_table: jax.Array,
                          pos: jax.Array,
                          sm_scale: Optional[float] = None) -> jax.Array:
    """Paged positioned-chunk attention oracle: gather the visible
    prefix's KV pages through the block table, then run the dense
    offset-causal reference.  q: [B, Hq, T, D]; k_pages/v_pages:
    [P, Hkv, page_size, D]; block_table: [B, NB]; pos: [B].  Numerically
    identical to chunk_attention over the equivalent contiguous cache:
    columns past pos[b] + t get exactly-zero softmax mass, so scratch-page
    content and ungranted pages can never leak in."""
    k = gather_kv_pages(k_pages, block_table)
    v = gather_kv_pages(v_pages, block_table)
    return chunk_attention(q, k, v, pos=pos, sm_scale=sm_scale)


def decode_attention_paged(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, *, block_table: jax.Array,
                           kv_len: Optional[jax.Array] = None,
                           sm_scale: Optional[float] = None) -> jax.Array:
    """Paged single-token decode oracle (gather pages, dense reference).

    q: [B, Hq, D]; k_pages/v_pages: [P, Hkv, page_size, D];
    block_table: [B, NB]; kv_len: [B] valid prefix lengths."""
    k = gather_kv_pages(k_pages, block_table)
    v = gather_kv_pages(v_pages, block_table)
    if kv_len is None:
        kv_len = jnp.full((q.shape[0],), k.shape[2], jnp.int32)
    return decode_attention(q, k, v, kv_len=kv_len, sm_scale=sm_scale)


def chunk_attention_paged_blocked(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, *,
                                  block_table: jax.Array, pos: jax.Array,
                                  sm_scale: Optional[float] = None
                                  ) -> jax.Array:
    """Flash-pattern PAGED chunk attention in pure jnp — the dry-run
    stand-in for the Pallas paged kernel: one page gathered per scan
    step (never the whole [B, NB*ps] cache), online softmax carried
    across pages.  Block k IS the page: the kernel's KV grid dimension
    walks block-table slots, and this mirrors that blocking exactly."""
    B, Hq, T, D = q.shape
    P, Hkv, ps, _ = k_pages.shape
    NB = block_table.shape[1]
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g * T, D)
    limit = pos[:, None] + jnp.arange(T)[None, :]          # [B, T]
    limit = jnp.tile(limit, (1, g))                        # rows are (g, t)

    def body(carry, ik):
        m, l, acc = carry
        page_ids = block_table[:, ik]                      # [B]
        kk = k_pages[page_ids].astype(jnp.float32)         # [B, Hkv, ps, D]
        vv = v_pages[page_ids].astype(jnp.float32)
        s = jnp.einsum("bhtd,bhkd->bhtk", qf, kk)
        cols = ik * ps + jnp.arange(ps)
        s = jnp.where(cols[None, None, None, :]
                      <= limit[:, None, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhtk,bhkd->bhtd", p, vv)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, g * T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g * T), jnp.float32)
    a0 = jnp.zeros((B, Hkv, g * T, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(NB))
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l[..., None]).reshape(B, Hkv, g, T, D)
    return o.reshape(B, Hq, T, D).astype(q.dtype)


def combine_decode_partials(o_parts, m_parts, l_parts):
    """Numerically-stable split-K combine of per-shard decode partials.

    o_parts: [K, B, H, D] unnormalized-then-normalized per-shard outputs
    (each o_k = softmax-local output), m/l: [K, B, H]. Standard flash-decode
    merge: rescale each shard by exp(m_k - m*) l_k and renormalize."""
    m_star = jnp.max(m_parts, axis=0)                       # [B, H]
    alpha = jnp.exp(m_parts - m_star[None])                 # [K, B, H]
    l_star = jnp.sum(alpha * l_parts, axis=0)               # [B, H]
    w = (alpha * l_parts) / l_star[None]
    return jnp.sum(o_parts * w[..., None], axis=0).astype(o_parts.dtype)


# -------------------------------------------------------------- rmsnorm ----
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    """y = x * rsqrt(mean(x^2) + eps) * w, reduction in f32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


# ----------------------------------------------------------- mamba2 SSD ----
def ssd_naive(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
              c: jax.Array, *, h0: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Naive sequential Mamba2 SSD recurrence — the ground-truth oracle.

    x: [B, L, H, P]  inputs per head
    dt: [B, L, H]    step sizes (already softplus'd, >= 0)
    a: [H]           negative decay rates
    b, c: [B, L, N]  input/output projections (single group)
    h0: [B, H, N, P] initial state
    returns (y [B, L, H, P], h_final [B, H, N, P])
    """
    B, L, H, P = x.shape
    N = b.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    bf, cf = b.astype(jnp.float32), c.astype(jnp.float32)
    af = a.astype(jnp.float32)
    h = jnp.zeros((B, H, N, P), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp            # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(af[None, :] * dt_t)  # [B,H]
        dbx = jnp.einsum("bh,bn,bhp->bhnp", dt_t, b_t, x_t)
        h = decay[..., None, None] * h + dbx
        y_t = jnp.einsum("bn,bhnp->bhp", c_t, h)
        return h, y_t

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    h, ys = jax.lax.scan(step, h, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, *, chunk: int = 128,
                h0: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD (Mamba2 'state-space dual' algorithm) in pure jnp.

    Mirrors the Pallas kernel's blocking exactly: within a chunk the output
    is a masked (C B^T ⊙ decay) @ (dt·x) matmul; across chunks a small state
    recurrence carries h. Validated against ssd_naive in tests."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    xf = x.astype(jnp.float32).reshape(B, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, chunk, H)
    bf = b.astype(jnp.float32).reshape(B, nc, chunk, N)
    cf = c.astype(jnp.float32).reshape(B, nc, chunk, N)
    af = a.astype(jnp.float32)

    ldec = af[None, None, None, :] * dtf                   # [B,nc,T,H]
    cum = jnp.cumsum(ldec, axis=2)                         # inclusive cumsum
    dtx = dtf[..., None] * xf                              # [B,nc,T,H,P]

    # intra-chunk: y[i] = sum_{j<=i} exp(cum[i]-cum[j]) (c_i . b_j) dtx[j]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [B,nc,T,T,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    m = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    g = jnp.einsum("bktn,bksn->bkts", cf, bf)              # [B,nc,T,T]
    y_intra = jnp.einsum("bkts,bktsh,bkshp->bkthp", g, m, dtx)

    # inter-chunk state recurrence over nc chunks
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [B,nc,H]
    # state contribution of chunk k: sum_j exp(cum[-1]-cum[j]) b_j ⊗ dtx[j]
    w = jnp.exp(cum[:, :, -1:, :] - cum)                   # [B,nc,T,H]
    s_in = jnp.einsum("bktn,bkth,bkthp->bkhnp", bf, w, dtx)

    h_init = (jnp.zeros((B, H, N, P), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def chunk_step(h, inp):
        dec_k, s_k = inp                                   # [B,H], [B,H,N,P]
        h_out = h                                          # state BEFORE chunk
        h = dec_k[..., None, None] * h + s_k
        return h, h_out

    dec_s = jnp.moveaxis(chunk_decay, 1, 0)
    sin_s = jnp.moveaxis(s_in, 1, 0)
    h_final, h_prevs = jax.lax.scan(chunk_step, h_init, (dec_s, sin_s))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                  # [B,nc,H,N,P]

    y_inter = jnp.einsum("bktn,bkth,bkhnp->bkthp",
                         cf, jnp.exp(cum), h_prevs)
    y = (y_intra + y_inter).reshape(B, L, H, P)
    return y.astype(x.dtype), h_final


# --------------------------------------------------------------- matmul ----
def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32-accumulating matmul oracle for the tiled-matmul demo kernel."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)
                   ).astype(a.dtype)
