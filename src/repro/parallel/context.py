"""Context parallelism for long-context decode: distributed split-K.

For long_500k (one query token vs a 524k KV cache, batch 1) neither batch
nor (often) heads can absorb the mesh — the cache SEQUENCE is the shardable
dim. The flash-decode split-K pattern maps onto the mesh:

  1. each rank runs decode attention over its LOCAL KV range, returning the
     unnormalized-softmax residuals (o_local, m_local, l_local) — the Pallas
     kernel (kernels/decode_attention.py) and the oracle both support
     return_residuals=True;
  2. one SMALL all-gather of the partials over the context axis
     ([shards, B, H(, D)] — KB not GB);
  3. the numerically-stable merge (kernels/ref.combine_decode_partials).

Wire cost: shards x (B·H·(D+2)) floats instead of gathering the cache
(B·H·S·D) — for zamba2 long_500k that is ~100 KB vs ~2.7 GB per shared-attn
invocation. Used via shard_map; tested for exactness against the unsharded
oracle in tests/test_context_parallel.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops, ref
from jax import shard_map


def _local_split_k(q, k_loc, v_loc, pos, *, axis: str, seq_shards: int,
                   impl: str):
    """Per-shard body: local residuals + gather + merge (runs in shard_map).

    q: [B, Hq_loc, D] (replicated over the context axis);
    k_loc/v_loc: [B, Hkv_loc, S/shards, D]; pos: [] global decode position.
    """
    B, Hq, D = q.shape
    s_loc = k_loc.shape[2]
    idx = jax.lax.axis_index(axis)
    start = idx * s_loc
    # local valid length: clamp (pos+1 - start) into [0, s_loc]
    kv_len = jnp.clip(pos + 1 - start, 0, s_loc)
    kv_len = jnp.broadcast_to(kv_len, (B,)).astype(jnp.int32)
    o, (m, l) = ops.decode_attention(q, k_loc, v_loc, kv_len=kv_len,
                                     impl=impl, return_residuals=True)
    # fully-masked shards contribute l=0 partials; combine handles them via
    # m=-inf weighting (exp(-inf)=0)
    m = jnp.where(kv_len[:, None] > 0, m, -1e30)
    with jax.named_scope("decode_splitk_gather"):
        o_all = jax.lax.all_gather(o, axis)          # [shards, B, Hq, D]
        m_all = jax.lax.all_gather(m, axis)
        l_all = jax.lax.all_gather(l, axis)
    return ref.combine_decode_partials(o_all, m_all, l_all)


def context_parallel_decode(q, k, v, pos, mesh: Mesh, *,
                            context_axis: str = "data",
                            head_axis: Optional[str] = "model",
                            impl: str = "auto"):
    """Decode attention with the KV cache sharded over `context_axis`.

    q: [B, Hq, D]; k, v: [B, Hkv, S, D] with S sharded over context_axis and
    heads (optionally) over head_axis. Returns [B, Hq, D] replicated over
    the context axis (sharded over the head axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shards = sizes.get(context_axis, 1)
    Hkv = k.shape[1]
    h_ax = head_axis if (head_axis and Hkv % sizes.get(head_axis, 1) == 0) \
        else None
    g = q.shape[1] // Hkv
    qspec = P(None, h_ax, None)
    kvspec = P(None, h_ax, context_axis, None)

    body = functools.partial(_local_split_k, axis=context_axis,
                             seq_shards=shards, impl=impl)
    fn = shard_map(body, mesh=mesh,
                       in_specs=(qspec, kvspec, kvspec, P()),
                       out_specs=qspec, check_vma=False)
    return fn(q, k, v, pos)
