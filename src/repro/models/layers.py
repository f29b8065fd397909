"""Shared model building blocks — pure-functional JAX, params as dicts.

Conventions:
  * init_* (key, cfg) -> param dict; leaf names match parallel/sharding.RULES.
  * apply functions are pure; dtype policy: params in cfg.param_dtype,
    compute in cfg.compute_dtype, reductions/softmax in f32.
  * every block wraps itself in jax.named_scope(<component>) — that is the
    XFA L3 hook: compiled-HLO collectives inherit the scope via op_name.
  * kernel hot-spots route through repro.kernels.ops (Pallas on TPU, oracle
    on CPU), which also registers analytic FLOPs with the XFA static layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.device_fold import annotate_cost
from repro.kernels import ops
from repro.parallel.axes import axis_size, shard

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Per-call runtime knobs threaded alongside the config."""
    cfg: ModelConfig
    impl: str = "auto"            # kernel impl: auto | ref | pallas
    fold_spec: Any = None         # DeviceFoldSpec or None
    decode: bool = False

    @property
    def cdtype(self):
        return jnp.dtype(self.cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ------------------------------------------------------------------ misc ----
def linear(p: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.einsum("...i,io->...o", x, p.astype(x.dtype))


@jax.custom_vjp
def _bf16_grad_barrier(x):
    """Identity whose COTANGENT is forced to bf16.

    f32 casts inside blocks (rope, silu, softmax) leak f32 cotangents back
    to the TP dx all-reduces (measured: every [B,S,d] backward all-reduce in
    the train HLO was f32 — EXPERIMENTS.md §Perf). Placing this barrier on
    block outputs halves that wire traffic; bf16 gradient reduction is
    standard practice at scale."""
    return x


def _bgb_fwd(x):
    return x, None


def _bgb_bwd(_, ct):
    return (ct.astype(jnp.bfloat16).astype(ct.dtype)
            if ct.dtype == jnp.float32 else ct,)


_bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


def grad_barrier(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if getattr(cfg, "bf16_grad_reduce", False):
        return _bf16_grad_barrier(x)
    return x


def init_norm(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    return {"scale": jnp.ones((d or cfg.d_model,), pdtype(cfg))}


def norm(p: Params, x: jax.Array, rt: Runtime) -> jax.Array:
    with jax.named_scope("norm"):
        return ops.rmsnorm(x, p["scale"], eps=rt.cfg.norm_eps, impl=rt.impl)


# ------------------------------------------------------------------ rope ----
def rope_tables(cfg: ModelConfig, positions: jax.Array, dim: int
                ) -> Tuple[jax.Array, jax.Array]:
    """positions [S] (or [B,S]) -> cos/sin [..., S, dim//2], f32."""
    half = dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, D]; cos/sin broadcastable to [..., S, D//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ------------------------------------------------------------- attention ----
def init_attention(key, cfg: ModelConfig) -> Params:
    d, h = cfg.d_model, cfg.head_dim_
    ks = jax.random.split(key, 6)
    dt = pdtype(cfg)
    if cfg.mla:
        qd = cfg.qk_nope_dim + cfg.qk_rope_dim
        p = {
            "wq": _init(ks[0], (d, cfg.n_heads * qd), dt),
            "wkv_a": _init(ks[1], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), dt),
            "wkv_b": _init(ks[2], (cfg.kv_lora_rank,
                                   cfg.n_heads * (cfg.qk_nope_dim
                                                  + cfg.v_head_dim)), dt),
            "wo": _init(ks[3], (cfg.n_heads * cfg.v_head_dim, d), dt),
        }
        return {"attn": p}
    p = {
        "wq": _init(ks[0], (d, cfg.n_heads * h), dt),
        "wk": _init(ks[1], (d, cfg.n_kv_heads * h), dt),
        "wv": _init(ks[2], (d, cfg.n_kv_heads * h), dt),
        "wo": _init(ks[3], (cfg.n_heads * h, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((h,), dt)
        p["k_norm"] = jnp.ones((h,), dt)
    return {"attn": p}


def update_cache_rows(dst: jax.Array, src: jax.Array, pos: jax.Array,
                      seq_axis: int = 2) -> jax.Array:
    """Vmapped ROW-RANGE cache scatter at arbitrary per-row offsets: row b
    of `src` (length T along `seq_axis`, T >= 1) lands at indices
    [pos[b], pos[b]+T) of `dst`'s seq_axis.  dst: [B, ...]; src: [B, ...];
    pos: [B].

    The vmap'd dynamic_update_slice is what lets every slot of a serving
    pool advance its cache row independently (continuous batching: slots
    decode at different depths in the same compiled step), and — with
    T > 1 — what lets a positioned CHUNK of prompt tokens land mid-row
    (in-model chunked prefill).  Callers must keep pos[b] + T within the
    row: dynamic_update_slice clamps the start index, so an overrun would
    silently shift the write onto earlier valid entries."""
    def one(d, s, p):
        idx = [jnp.int32(0)] * d.ndim
        idx[seq_axis - 1] = p        # batch dim vmapped away
        return jax.lax.dynamic_update_slice(d, s, tuple(idx))
    return jax.vmap(one)(dst, src.astype(dst.dtype), pos)


def update_cache_pages(arena: jax.Array, src: jax.Array, pos: jax.Array,
                       block_table: jax.Array,
                       seq_axis: int = 2) -> jax.Array:
    """Paged cache scatter: the PAGE-ARENA twin of update_cache_rows.

    arena: [P, ..., page_size, ...] page pool (page id replaces the batch
    dim; `seq_axis` is the row-within-page axis); src: [B, ..., T, ...]
    fresh rows; pos: [B] per-row virtual offsets; block_table: [B, NB]
    int32 page ids mapping virtual page `v` of row b to arena page
    block_table[b, v].

    Virtual row pos[b]+t of batch row b lands at
    (block_table[b, (pos[b]+t) // page_size], (pos[b]+t) % page_size).
    Page 0 is the engine's reserved scratch page: bucket-pad rows and
    past-frontier writes of a padded chunk resolve there (their table
    entries are 0) and are overwritten or masked before any read — the
    same discard contract dense pads have, made page-granular.

    Every axis up to the row axis is indexed (page, then each axis
    between page and row, e.g. the GQA head axis, then row), so only the
    axes after the row are the scatter's window: the write lands in the
    arena's own layout and needs no re-laid-out copy of it."""
    ps = arena.shape[seq_axis]
    NB = block_table.shape[1]
    B = src.shape[0]
    T = src.shape[seq_axis]
    abs_pos = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    blk = jnp.clip(abs_pos // ps, 0, NB - 1)
    pg = jnp.take_along_axis(jnp.asarray(block_table, jnp.int32), blk, axis=1)
    row = abs_pos % ps
    # [B, ..., T, ...] -> [B*T, mid..., tail...] matching the advanced-index
    # selection shape: page and row ids [B*T, 1...] broadcast against an
    # iota per mid axis to [B*T, mid...]; the tail is the window
    mid = arena.shape[1:seq_axis]
    srcf = jnp.moveaxis(src, seq_axis, 1).reshape(
        (B * T,) + src.shape[1:seq_axis] + src.shape[seq_axis + 1:])
    lead = (B * T,) + (1,) * len(mid)
    index = [pg.reshape(lead)]
    for i, n in enumerate(mid):
        shape = [1] * len(lead)
        shape[i + 1] = n
        index.append(jnp.arange(n, dtype=jnp.int32).reshape(shape))
    index.append(row.reshape(lead))
    return arena.at[tuple(index)].set(srcf.astype(arena.dtype))


def layer_pages(arena: Params, block_table: jax.Array, layer: jax.Array
                ) -> Tuple[Params, jax.Array]:
    """This layer's view of the stacked page arena: every leaf [L, P, ...]
    flattened to [L*P, ...] (a bitcast) and the block table offset to
    layer `layer`'s pages, block_table + layer*P.  Page layer*P is the
    layer's own scratch page, so the page-0 contract holds per layer."""
    L, P = jax.tree.leaves(arena)[0].shape[:2]
    flat = jax.tree.map(lambda a: a.reshape((L * P,) + a.shape[2:]), arena)
    return flat, jnp.asarray(block_table, jnp.int32) + layer * P


def last_valid(x: jax.Array, valid: Optional[jax.Array]) -> jax.Array:
    """x: [B, T, d] -> [B, 1, d] at each row's last VALID position.  A
    bucket-padded chunk carries valid: [B] real-token counts; the logits a
    caller samples from must come from the last real token, not the pad."""
    if valid is None:
        return x[:, -1:]
    last = jnp.clip(jnp.asarray(valid, jnp.int32) - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: int, dtype) -> Params:
    """Stacked (scan-compatible) KV cache for n_layers layers."""
    h = cfg.head_dim_
    if cfg.mla:
        return {
            "ckv": jnp.zeros((n_layers, batch, max_len, cfg.kv_lora_rank),
                             dtype),
            "krope": jnp.zeros((n_layers, batch, max_len, cfg.qk_rope_dim),
                               dtype),
        }
    return {
        "k": jnp.zeros((n_layers, batch, cfg.n_kv_heads, max_len, h), dtype),
        "v": jnp.zeros((n_layers, batch, cfg.n_kv_heads, max_len, h), dtype),
    }


def attention(p: Params, x: jax.Array, rt: Runtime, positions: jax.Array,
              cache: Optional[Params] = None, pos: Optional[jax.Array] = None,
              kv: Optional[jax.Array] = None, causal: bool = True,
              return_kv: bool = False,
              block_table: Optional[jax.Array] = None,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, Optional[Params]]:
    """GQA/MQA (optionally qk-norm) attention.

    x: [B, S, d]; kv: cross-attention source [B, Sk, d] (None = self-attn);
    cache+pos: single-layer KV cache in positioned-chunk mode — pos is [B]
    int32, each batch row's own cache depth (a scalar broadcasts): the S
    fresh K/V rows are scattered at [pos, pos+S) of each row's cache and
    queries attend offset-causally against the row's full prefix.  S == 1
    is the pooled decode step, S > 1 an in-model prefill chunk — the same
    operation at different widths;
    block_table: [B, NB] int32 page ids — when given, `cache` is the PAGE
    ARENA of every layer ([L, P, Hkv, page_size, h]) rather than one
    layer's per-row storage, and `layer` is this layer's index in it:
    writes scatter and reads gather through the table offset to the
    layer's pages (layer_pages), so a row only touches the pages it was
    granted and the arena is updated in place;
    positions: [S] shared rope positions, or [B, S] per-row (chunk/decode);
    return_kv: return this call's post-rope K/V (prefill cache building).
    Returns (y [B, S, d], cache-or-kv).
    """
    if rt.cfg.mla:
        return mla_attention(p, x, rt, positions, cache, pos,
                             return_kv=return_kv, block_table=block_table,
                             layer=layer)
    cfg = rt.cfg
    ap = p["attn"]
    B, S, d = x.shape
    h = cfg.head_dim_
    with jax.named_scope("attention"):
        q = linear(ap["wq"], x).reshape(B, S, cfg.n_heads, h)
        src = x if kv is None else kv
        Sk = src.shape[1]
        k = linear(ap["wk"], src).reshape(B, Sk, cfg.n_kv_heads, h)
        v = linear(ap["wv"], src).reshape(B, Sk, cfg.n_kv_heads, h)
        annotate_cost("attention", "attention", "qkv_proj",
                      flops=2.0 * B * S * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * h)
        if cfg.qk_norm:
            q = ops.rmsnorm(q, ap["q_norm"], eps=cfg.norm_eps, impl=rt.impl)
            k = ops.rmsnorm(k, ap["k_norm"], eps=cfg.norm_eps, impl=rt.impl)
        if kv is None:  # RoPE on self-attention only
            with jax.named_scope("rope"):
                cos, sin = rope_tables(cfg, positions, h)
                if cos.ndim == 3:            # per-row positions [B, S]
                    cos, sin = cos[:, None], sin[:, None]
                q = apply_rope(q.swapaxes(1, 2), cos, sin)       # [B,H,S,h]
                k = apply_rope(k.swapaxes(1, 2), cos, sin)
        else:
            q = q.swapaxes(1, 2)
            k = k.swapaxes(1, 2)
        v = v.swapaxes(1, 2)
        q = shard(q, "batch", "model", None, None)
        k = shard(k, "batch", "model" if cfg.n_kv_heads > 1 else None,
                  None, None)

        if cache is not None and block_table is not None:
            # paged positioned chunk: scatter the S fresh rows through the
            # layer's block table into the flat arena of every layer, read
            # back the row's visible prefix through the same indirection
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
            flat, bt = layer_pages(cache, block_table, layer)
            ck = update_cache_pages(flat["k"], k, pos, bt, seq_axis=2)
            cv = update_cache_pages(flat["v"], v, pos, bt, seq_axis=2)
            if S == 1:                 # decode width: paged flash-decode
                o = ops.decode_attention_paged(
                    q[:, :, 0], ck, cv, block_table=bt,
                    kv_len=pos + 1, impl=rt.impl)
                o = o.reshape(B, 1, cfg.n_heads, h)
            else:                      # prefill chunk at per-row offsets
                o = ops.chunk_attention_paged(
                    q, ck, cv, block_table=bt, pos=pos, impl=rt.impl)
                o = o.swapaxes(1, 2)                   # [B,S,Hq,h]
            new_cache = {"k": ck.reshape(cache["k"].shape),
                         "v": cv.reshape(cache["v"].shape)}
        elif cache is not None:
            # positioned chunk: append each row's S fresh k/v rows at its
            # own `pos`, attend to the row's own prefix (offset-causal)
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
            ck = update_cache_rows(cache["k"], k, pos, seq_axis=2)
            cv = update_cache_rows(cache["v"], v, pos, seq_axis=2)
            if S == 1:                 # decode width: flash-decode kernel
                kv_len = pos + 1
                o = ops.decode_attention(q[:, :, 0], ck, cv, kv_len=kv_len,
                                         impl=rt.impl)
                o = o[:, None] if o.ndim == 3 else o   # [B,1,Hq,h] fmt below
                o = o.reshape(B, 1, cfg.n_heads, h)
            else:                      # prefill chunk at per-row offsets
                o = ops.chunk_attention(q, ck, cv, pos=pos, impl=rt.impl)
                o = o.swapaxes(1, 2)                   # [B,S,Hq,h]
            new_cache = {"k": ck, "v": cv}
        else:
            o = ops.attention(q, k, v, causal=causal and kv is None,
                              impl=rt.impl)
            o = o.swapaxes(1, 2)                                 # [B,S,Hq,h]
            new_cache = {"k": k, "v": v} if return_kv else None
        y = linear(ap["wo"], o.reshape(B, S, cfg.n_heads * h))
        annotate_cost("attention", "attention", "o_proj",
                      flops=2.0 * B * S * cfg.n_heads * h * d)
        return shard(y, "batch", "seq", None), new_cache


def mla_attention(p: Params, x: jax.Array, rt: Runtime, positions: jax.Array,
                  cache: Optional[Params] = None,
                  pos: Optional[jax.Array] = None,
                  return_kv: bool = False,
                  block_table: Optional[jax.Array] = None,
                  layer: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Optional[Params]]:
    """Multi-head Latent Attention (DeepSeek-V2).

    Prefill/train: expand the latent into full per-head K/V.
    Decode: matrix-absorbed latent attention — the cache stores ONLY
    (c_kv [B,S,r], k_rope [B,S,dr]); queries are projected into the latent
    space, and the decode kernel runs with a single latent 'kv head'.
    With block_table the latent cache is the page arena of every layer
    ([L, P, page_size, r] / [L, P, page_size, dr]) addressed exactly like
    the GQA one — the latent rows page the same way full K/V rows do."""
    cfg = rt.cfg
    ap = p["attn"]
    B, S, d = x.shape
    nh, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    with jax.named_scope("attention"):
        q = linear(ap["wq"], x).reshape(B, S, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        kv_a = linear(ap["wkv_a"], x)                      # [B,S,r+dr]
        c_kv, k_rope = kv_a[..., :r], kv_a[..., r:]
        with jax.named_scope("rope"):
            cos, sin = rope_tables(cfg, positions, dr)
            if cos.ndim == 3:                # per-row positions [B, S]
                cos, sin = cos[:, None], sin[:, None]
            q_rope = apply_rope(q_rope.swapaxes(1, 2), cos, sin)  # [B,nh,S,dr]
            k_rope = apply_rope(k_rope[:, None], cos, sin)        # [B,1,S,dr]
        annotate_cost("attention", "attention", "mla_proj",
                      flops=2.0 * B * S * d * (nh * (dn + dr) + r + dr))

        wkv_b = ap["wkv_b"].reshape(r, nh, dn + dv)
        wk_b, wv_b = wkv_b[..., :dn], wkv_b[..., dn:]      # [r,nh,dn],[r,nh,dv]

        if cache is not None:
            # positioned chunk in LATENT space: scatter this chunk's S
            # latent rows at per-row offsets, matrix-absorb the queries,
            # run the decode kernel (S == 1) or the offset-causal chunk
            # kernel (S > 1) over the single latent 'kv head'
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
            if block_table is not None:
                flat, bt = layer_pages(cache, block_table, layer)
                new_cache = {
                    "ckv": update_cache_pages(flat["ckv"], c_kv, pos, bt,
                                              seq_axis=1),
                    "krope": update_cache_pages(flat["krope"], k_rope[:, 0],
                                                pos, bt, seq_axis=1)}
                new_cache = jax.tree.map(lambda f, a: f.reshape(a.shape),
                                         new_cache, cache)
                # the kernel operands below hold this layer's pages only,
                # read through the layer's own (un-offset) block table
                cc, cr = new_cache["ckv"][layer], new_cache["krope"][layer]
            else:
                cc = update_cache_rows(cache["ckv"], c_kv, pos, seq_axis=1)
                cr = update_cache_rows(cache["krope"], k_rope[:, 0], pos,
                                       seq_axis=1)
                new_cache = {"ckv": cc, "krope": cr}
            # absorb: q_latent = q_nope @ wk_b^T  -> [B,nh,S,r]
            q_lat = jnp.einsum("bhtd,rhd->bhtr",
                               q_nope.swapaxes(1, 2).astype(jnp.float32),
                               wk_b.astype(jnp.float32)).astype(x.dtype)
            q_full = jnp.concatenate([q_lat, q_rope], -1)   # [B,nh,S,r+dr]
            # [B,1,Smax,r+dr] dense; [P,1,page,r+dr] paged arena — the
            # added axis is the single latent 'kv head' either way
            k_full = jnp.concatenate([cc, cr], -1)[:, None]
            # v = c_kv (latent); pad to r+dr so k/v share a kernel shape
            v_lat = jnp.pad(cc, ((0, 0), (0, 0), (0, dr)))[:, None]
            scale = (dn + dr) ** -0.5
            if block_table is not None:
                if S == 1:
                    o_lat = ops.decode_attention_paged(
                        q_full[:, :, 0], k_full, v_lat,
                        block_table=block_table, kv_len=pos + 1,
                        sm_scale=scale, impl=rt.impl)[:, None]
                else:
                    o_lat = ops.chunk_attention_paged(
                        q_full, k_full, v_lat, block_table=block_table,
                        pos=pos, sm_scale=scale,
                        impl=rt.impl).swapaxes(1, 2)
            elif S == 1:
                kv_len = pos + 1
                o_lat = ops.decode_attention(
                    q_full[:, :, 0], k_full, v_lat, kv_len=kv_len,
                    sm_scale=scale, impl=rt.impl)[:, None]   # [B,1,nh,r+dr]
            else:
                o_lat = ops.chunk_attention(
                    q_full, k_full, v_lat, pos=pos, sm_scale=scale,
                    impl=rt.impl).swapaxes(1, 2)             # [B,S,nh,r+dr]
            o_lat = o_lat[..., :r]
            o = jnp.einsum("bthr,rhd->bthd", o_lat.astype(jnp.float32),
                           wv_b.astype(jnp.float32)).astype(x.dtype)
        else:
            from repro.parallel.axes import shard_dims
            _ch = lambda t: shard_dims(t, {0: "batch", 1: "model"})
            # expand the latent in COMPUTE dtype with heads pinned to the TP
            # axis: the f32-staged version produced a 2.1 GB f32 all-gather
            # per layer (220 GB/step on deepseek train_4k — EXPERIMENTS.md
            # §Perf deepseek iteration 2)
            k_nope = _ch(jnp.einsum("bsr,rhd->bhsd", c_kv,
                                    wk_b.astype(c_kv.dtype)))
            v = _ch(jnp.einsum("bsr,rhd->bhsd", c_kv,
                               wv_b.astype(c_kv.dtype)))
            k = _ch(jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, nh, S, dr))], -1))
            qq = _ch(jnp.concatenate([q_nope.swapaxes(1, 2), q_rope], -1))
            # pad v (dv) up to qk dim so the flash kernel sees equal D
            dq = dn + dr
            v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dq - dv)))
            o = ops.attention(qq, k, v_p, causal=True, sm_scale=dq ** -0.5,
                              impl=rt.impl)[..., :dv]
            o = o.swapaxes(1, 2)                                    # [B,S,nh,dv]
            new_cache = ({"ckv": c_kv, "krope": k_rope[:, 0]}
                         if return_kv else None)
        y = linear(ap["wo"], o.reshape(B, S, nh * dv))
        return shard(y, "batch", "seq", None), new_cache


# ------------------------------------------------------------------- mlp ----
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = pdtype(cfg)
    p = {"w_up": _init(ks[1], (d, f), dt), "w_down": _init(ks[2], (f, d), dt)}
    if cfg.mlp_gated:
        p["w_gate"] = _init(ks[0], (d, f), dt)
    return {"mlp": p}


def mlp(p: Params, x: jax.Array, rt: Runtime) -> jax.Array:
    mp = p["mlp"]
    cfg = rt.cfg
    with jax.named_scope("mlp"):
        if getattr(cfg, "manual_tp", False):
            from repro.parallel.tp import col_row_mlp, manual_tp_available
            f = mp["w_up"].shape[1]
            if manual_tp_available(f):
                nmat = 3 if cfg.mlp_gated else 2
                annotate_cost("mlp", "mlp", "ffn",
                              flops=2.0 * x.shape[0] * x.shape[1]
                              * cfg.d_model * f * nmat)
                y = col_row_mlp(x, mp["w_up"], mp["w_down"],
                                mp.get("w_gate"), cfg.mlp_gated)
                return shard(y, "batch", "seq", None)
        up = linear(mp["w_up"], x)
        if cfg.mlp_gated:
            act = jax.nn.silu(linear(mp["w_gate"], x).astype(jnp.float32))
            hidden = (act * up.astype(jnp.float32)).astype(x.dtype)
        else:
            hidden = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
        hidden = shard(hidden, "batch", "seq", "model")
        y = linear(mp["w_down"], hidden)
        f = mp["w_up"].shape[1]
        nmat = 3 if cfg.mlp_gated else 2
        annotate_cost("mlp", "mlp", "ffn",
                      flops=2.0 * x.shape[0] * x.shape[1] * cfg.d_model * f * nmat)
        return shard(y, "batch", "seq", None)


# ----------------------------------------------------------------- embed ----
def init_embed(key, cfg: ModelConfig) -> Params:
    return {"embed": {"table": _init(key, (cfg.vocab, cfg.d_model),
                                     pdtype(cfg), scale=1.0)}}


def embed(p: Params, tokens: jax.Array, rt: Runtime) -> jax.Array:
    with jax.named_scope("embed"):
        x = jnp.take(p["embed"]["table"], tokens, axis=0).astype(rt.cdtype)
        annotate_cost("embed", "embed", "lookup", bytes=float(x.size * 2))
        return shard(x, "batch", "seq", None)


def init_lm_head(key, cfg: ModelConfig) -> Params:
    if cfg.tie_embeddings:
        return {}
    return {"lm_head": {"w": _init(key, (cfg.d_model, cfg.vocab), pdtype(cfg))}}


def lm_head(p: Params, x: jax.Array, rt: Runtime) -> jax.Array:
    with jax.named_scope("lm_head"):
        w = (p["embed"]["table"].T if rt.cfg.tie_embeddings
             else p["lm_head"]["w"])
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        annotate_cost("lm_head", "lm_head", "proj",
                      flops=2.0 * x.shape[0] * x.shape[1] * rt.cfg.d_model
                      * rt.cfg.vocab)
        return shard(logits, "batch", "seq", "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL in f32; mask: [B, S] 1=count.

    Vocab-sharding safe: the gold logit is extracted by a one-hot
    CONTRACTION over the vocab dim (fuses to iota+select+reduce and keeps
    the vocab dim sharded under SPMD), never a take_along_axis gather that
    would force an all-gather of [B, S, V] logits."""
    with jax.named_scope("loss"):
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=lf.dtype)
        gold = jnp.einsum("bsv,bsv->bs", lf, onehot)
        nll = lse - gold
        if mask is None:
            return jnp.mean(nll)
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
