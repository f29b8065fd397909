"""jit'd public wrappers for the Pallas kernels, with impl dispatch.

impl='auto'   -> Pallas kernel on TPU, pure-jnp reference elsewhere (CPU CI)
impl='pallas' -> Pallas kernel (interpret=True off-TPU: Python-executed, used
                 by the allclose test sweeps)
impl='ref'    -> pure-jnp oracle (ref.py)

Every wrapper registers its analytic FLOPs/bytes with the XFA static-cost
layer (core.device_fold.annotate_cost) under the component that calls it —
kernels are cross-flow callees like any library API in the paper.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.device_fold import annotate_cost
from repro.core import tracer as xfa
from repro.parallel.axes import (axis_size, dims_spec, get_runtime_mesh,
                                 in_manual_region)

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import mamba_scan as _ssd
from . import ref
from . import rmsnorm as _rms


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def _bytes(*arrs) -> float:
    return float(sum(a.size * a.dtype.itemsize for a in arrs))


def _per_device(kernel, args, dims, n_out: int = 1):
    """Run a Mosaic kernel under the ambient mesh.  XLA cannot partition
    a Mosaic kernel, so each device runs it on its own block through
    shard_map: args are split by `dims` ({dim: logical axis} per arg,
    see parallel.axes.dims_spec) and every output has the layout of the
    first arg.  Without a mesh, or inside a shard_map body already, the
    kernel is called as is."""
    if get_runtime_mesh() is None or in_manual_region():
        return kernel(*args)
    specs = tuple(dims_spec(a.shape, d) for a, d in zip(args, dims))
    out_specs = specs[0] if n_out == 1 else (specs[0],) * n_out
    return jax.shard_map(kernel, mesh=get_runtime_mesh(), in_specs=specs,
                         out_specs=out_specs, check_vma=False)(*args)


def attention(q, k, v, *, causal: bool = True,
              sm_scale: Optional[float] = None, logit_softcap: float = 0.0,
              impl: str = "auto", interpret: Optional[bool] = None,
              component: str = "attention") -> jax.Array:
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    flops = 4.0 * B * Hq * Sq * Sk * D * (0.5 if causal and Sq == Sk else 1.0)
    annotate_cost(xfa.current_component(), component, "flash_attention",
                  flops=flops, bytes=_bytes(q, k, v) * 2)
    mode = _resolve(impl)
    if mode == "ref":
        return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale,
                             logit_softcap=logit_softcap,
                             q_offset=Sk - Sq if causal else 0)
    if mode == "chunked":
        # flash-pattern jnp path: used by the dry-run (Mosaic cannot lower on
        # the CPU backend) — same FLOPs/live-memory shape as the kernel
        return ref.attention_chunked(q, k, v, causal=causal,
                                     sm_scale=sm_scale,
                                     logit_softcap=logit_softcap,
                                     q_offset=Sk - Sq if causal else 0)
    itp = (not _on_tpu()) if interpret is None else interpret
    kernel = functools.partial(_fa.flash_attention, causal=causal,
                               sm_scale=sm_scale,
                               logit_softcap=logit_softcap, interpret=itp)
    # heads split over 'model' only where q AND kv heads both divide it,
    # so each device keeps whole GQA groups
    m = axis_size("model")
    dims = {0: "batch", 1: "model"} \
        if q.shape[1] % m == 0 and k.shape[1] % m == 0 else {0: "batch"}
    return _per_device(kernel, (q, k, v), (dims, dims, dims))


def decode_attention(q, k, v, *, kv_len=None, sm_scale=None,
                     impl: str = "auto", interpret: Optional[bool] = None,
                     return_residuals: bool = False,
                     component: str = "attention"):
    B, Hq, D = q.shape
    S = k.shape[2]
    annotate_cost(xfa.current_component(), component, "decode_attention",
                  flops=4.0 * B * Hq * S * D, bytes=_bytes(k, v))
    mode = _resolve(impl)
    if mode in ("ref", "chunked"):
        return ref.decode_attention(q, k, v, kv_len=kv_len, sm_scale=sm_scale,
                                    return_residuals=return_residuals)
    itp = (not _on_tpu()) if interpret is None else interpret
    return _dec.decode_attention(q, k, v, kv_len=kv_len, sm_scale=sm_scale,
                                 interpret=itp,
                                 return_residuals=return_residuals)


def chunk_attention(q, k, v, *, pos, sm_scale=None, impl: str = "auto",
                    interpret: Optional[bool] = None,
                    component: str = "attention") -> jax.Array:
    """Positioned-chunk attention: q [B, Hq, T, D] at per-row cache
    offsets pos [B]; k, v [B, Hkv, S, D] the full cache (this chunk's
    rows already scattered at [pos, pos+T)).  Query t of row b attends
    columns <= pos[b] + t — the offset-causal mask that makes prefill and
    decode the same operation at different widths."""
    B, Hq, T, D = q.shape
    S = k.shape[2]
    annotate_cost(xfa.current_component(), component, "chunk_attention",
                  flops=4.0 * B * Hq * T * S * D, bytes=_bytes(k, v))
    mode = _resolve(impl)
    if mode == "ref":
        return ref.chunk_attention(q, k, v, pos=pos, sm_scale=sm_scale)
    if mode == "chunked":
        # flash-pattern jnp path for the dry-run: O(T·block_k) live scores,
        # same footprint shape as the kernel
        return ref.chunk_attention_blocked(q, k, v, pos=pos,
                                           sm_scale=sm_scale)
    itp = (not _on_tpu()) if interpret is None else interpret
    return _dec.chunk_attention(q, k, v, pos=pos, sm_scale=sm_scale,
                                interpret=itp)


def decode_attention_paged(q, k_pages, v_pages, *, block_table, kv_len,
                           sm_scale=None, impl: str = "auto",
                           interpret: Optional[bool] = None,
                           component: str = "attention") -> jax.Array:
    """Paged single-token decode: q [B, Hq, D] against a page arena
    k_pages/v_pages [P, Hkv, page_size, D] addressed through block_table
    [B, NB] (int32 page ids; unassigned slots point at the reserved
    scratch page 0 and are masked by kv_len [B])."""
    B, Hq, D = q.shape
    P, _, ps, _ = k_pages.shape
    NB = block_table.shape[1]
    # cost model charges the VISIBLE prefix, not the arena: each row
    # streams at most NB pages of its own table
    annotate_cost(xfa.current_component(), component, "decode_attention_paged",
                  flops=4.0 * B * Hq * NB * ps * D,
                  bytes=2.0 * B * NB * ps * D * k_pages.dtype.itemsize)
    mode = _resolve(impl)
    if mode in ("ref", "chunked"):
        return ref.decode_attention_paged(q, k_pages, v_pages,
                                          block_table=block_table,
                                          kv_len=kv_len, sm_scale=sm_scale)
    itp = (not _on_tpu()) if interpret is None else interpret
    return _dec.decode_attention_paged(q, k_pages, v_pages,
                                       block_table=block_table,
                                       kv_len=kv_len, sm_scale=sm_scale,
                                       interpret=itp)


def chunk_attention_paged(q, k_pages, v_pages, *, block_table, pos,
                          sm_scale=None, impl: str = "auto",
                          interpret: Optional[bool] = None,
                          component: str = "attention") -> jax.Array:
    """Paged positioned-chunk attention: q [B, Hq, T, D] at per-row
    offsets pos [B]; KV lives in the page arena [P, Hkv, page_size, D]
    and each row's visible prefix is gathered through block_table
    [B, NB].  Same offset-causal mask as chunk_attention — the paged
    pool changes where rows live, never what a query sees."""
    B, Hq, T, D = q.shape
    P, _, ps, _ = k_pages.shape
    NB = block_table.shape[1]
    annotate_cost(xfa.current_component(), component, "chunk_attention_paged",
                  flops=4.0 * B * Hq * T * NB * ps * D,
                  bytes=2.0 * B * NB * ps * D * k_pages.dtype.itemsize)
    mode = _resolve(impl)
    if mode == "ref":
        return ref.chunk_attention_paged(q, k_pages, v_pages,
                                         block_table=block_table,
                                         pos=pos, sm_scale=sm_scale)
    if mode == "chunked":
        # blocked-jnp dry-run path: one page of live scores at a time,
        # same footprint shape as the Pallas kernel
        return ref.chunk_attention_paged_blocked(q, k_pages, v_pages,
                                                 block_table=block_table,
                                                 pos=pos, sm_scale=sm_scale)
    itp = (not _on_tpu()) if interpret is None else interpret
    return _dec.chunk_attention_paged(q, k_pages, v_pages,
                                      block_table=block_table,
                                      pos=pos, sm_scale=sm_scale,
                                      interpret=itp)


def rmsnorm(x, w, *, eps: float = 1e-5, impl: str = "auto",
            interpret: Optional[bool] = None,
            component: str = "norm") -> jax.Array:
    annotate_cost(xfa.current_component(), component, "rmsnorm",
                  flops=4.0 * x.size, bytes=2.0 * _bytes(x))
    mode = _resolve(impl)
    if mode in ("ref", "chunked"):
        return ref.rmsnorm(x, w, eps=eps)
    itp = (not _on_tpu()) if interpret is None else interpret
    kernel = functools.partial(_rms.rmsnorm, eps=eps, interpret=itp)
    return _per_device(kernel, (x, w), ({0: "batch"}, {}))


def rmsnorm_add(x, residual, w, *, eps: float = 1e-5, impl: str = "auto",
                interpret: Optional[bool] = None, component: str = "norm"):
    annotate_cost(xfa.current_component(), component, "rmsnorm_add",
                  flops=5.0 * x.size, bytes=3.0 * _bytes(x))
    mode = _resolve(impl)
    if mode in ("ref", "chunked"):
        s = x + residual
        return ref.rmsnorm(s, w, eps=eps), s
    itp = (not _on_tpu()) if interpret is None else interpret
    kernel = functools.partial(_rms.rmsnorm_add, eps=eps, interpret=itp)
    return _per_device(kernel, (x, residual, w),
                       ({0: "batch"}, {0: "batch"}, {}), n_out=2)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128, h0=None,
             impl: str = "auto", interpret: Optional[bool] = None,
             component: str = "ssm"):
    """Mamba2 SSD: x [B,L,H,P], dt [B,L,H], a [H], b/c [B,L,N];
    h0 [B,H,N,P] carried state (None = fresh sequence) — chunked prefill
    resumes the recurrence exactly where the previous chunk stopped.
    Returns (y [B,L,H,P], h_final [B,H,N,P])."""
    B, L, H, P = x.shape
    N = b.shape[-1]
    # 2 matmul pairs of [T,T]x[T,*] per chunk ~ 6*B*H*L*chunk*(N+P) flops
    annotate_cost(xfa.current_component(), component, "ssd_scan",
                  flops=float(6 * B * H * L * chunk * (N + P)),
                  bytes=_bytes(x, dt, b, c) * 2)
    mode = _resolve(impl)
    # pad L to a chunk multiple: dt=0 rows decay by exp(0)=1 and inject 0,
    # so state and valid outputs are untouched
    pad = (-L) % chunk
    if pad:
        zp = lambda a: jnp.pad(a, [(0, pad if i == 1 else 0)
                                   for i in range(a.ndim)])
        x, dt, b, c = zp(x), zp(dt), zp(b), zp(c)
    if mode in ("ref", "chunked"):
        y, h = ref.ssd_chunked(x, dt, a, b, c, chunk=chunk, h0=h0)
    else:
        itp = (not _on_tpu()) if interpret is None else interpret
        dtf = dt.astype(jnp.float32)
        dtx = (dtf[..., None] * x.astype(jnp.float32)).astype(x.dtype)
        ldec = a.astype(jnp.float32)[None, None, :] * dtf    # [B, L, H]
        # to head-major layout for plain-slice BlockSpecs
        dtx = jnp.moveaxis(dtx, 2, 1)                        # [B, H, L, P]
        ldec = jnp.moveaxis(ldec, 2, 1)                      # [B, H, L]
        y, h = _ssd.ssd_scan(dtx, ldec, b, c, chunk=chunk, h0=h0,
                             interpret=itp)
        y = jnp.moveaxis(y, 1, 2)
    if pad:
        y = y[:, :L]
    return y, h
