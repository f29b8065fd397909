"""Decoder-only transformer LM (dense + MoE) with scan-over-layers.

Structure decisions that matter at scale:
  * jax.lax.scan over stacked layer params: one compiled layer body per
    block family regardless of depth -> small HLO, tractable dry-run
    compiles, and the remat policy applies per scanned body.
  * the XFA device fold table rides in the scan carry; MoE layers emit
    data-dependent metrics into it.
  * trace-time static costs use core.device_fold.scan_multiplier so one
    traced body registers L layers' worth of analytic FLOPs.
  * KV caches are stacked [L, ...] pytrees scanned together with the params
    (the paged arena instead rides whole in the scan carry and is updated
    in place); prefill and decode share ONE positioned-chunk body
    (forward_chunk) — a chunk of T tokens lands at per-row cache offsets,
    T = 1 being the pooled decode tick and pos = 0, T = S being bulk
    prefill.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.device_fold import DeviceFoldSpec, scan_multiplier
from repro.parallel.axes import shard

from . import moe as moe_lib
from .layers import (Params, Runtime, attention, cross_entropy, embed,
                     init_attention, init_embed, init_kv_cache, init_lm_head,
                     init_mlp, init_norm, last_valid, lm_head, mlp, norm)


# ------------------------------------------------------------ one layer ----
def init_decoder_layer(key, cfg: ModelConfig, kind: str) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
    p.update(init_attention(k1, cfg))
    if kind == "moe":
        p.update(moe_lib.init_moe(k2, cfg))
    else:
        p.update(init_mlp(k2, cfg))
    return p


def decoder_layer(p: Params, x: jax.Array, rt: Runtime, table: jax.Array,
                  positions: jax.Array, kind: str,
                  cache: Optional[Params] = None,
                  pos: Optional[jax.Array] = None,
                  return_kv: bool = False,
                  block_table: Optional[jax.Array] = None,
                  layer: Optional[jax.Array] = None):
    """Pre-norm block. Returns (x, table, aux, new_cache).  With a
    block_table, `cache` is the page arena of every layer and `layer`
    this block's index in it."""
    h = norm(p["norm1"], x, rt)
    a, new_cache = attention(p, h, rt, positions, cache=cache, pos=pos,
                             block_table=block_table, layer=layer)
    x = x + a
    h = norm(p["norm2"], x, rt)
    if kind == "moe":
        y, table, aux = moe_lib.moe(p, h, rt, table)
    else:
        y = mlp(p, h, rt)
        aux = jnp.float32(0.0)
    x = x + y
    return shard(x, "batch", "seq", None), table, aux, new_cache


# ------------------------------------------------------------ full model ----
def _layer_kinds(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    """Layer stacks in order: ((kind, count), ...)."""
    if cfg.moe:
        k = cfg.first_dense_layers
        stacks = []
        if k:
            stacks.append(("dense", k))
        stacks.append(("moe", cfg.n_layers - k))
        return tuple(stacks)
    return (("dense", cfg.n_layers),)


def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 3 + len(_layer_kinds(cfg)))
    p: Dict[str, Any] = {}
    p.update(init_embed(keys[0], cfg))
    p.update(init_lm_head(keys[1], cfg))
    p["final_norm"] = init_norm(cfg)
    if cfg.family == "vlm":
        p.update(init_frontend(keys[2], cfg))
    for i, (kind, count) in enumerate(_layer_kinds(cfg)):
        lkeys = jax.random.split(keys[3 + i], count)
        stack = jax.vmap(
            functools.partial(init_decoder_layer, cfg=cfg, kind=kind))(lkeys)
        p[f"stack_{kind}" if cfg.moe else "stack"] = {"stack": stack}
    return p


def _stacks(p: Params, cfg: ModelConfig):
    for kind, count in _layer_kinds(cfg):
        name = f"stack_{kind}" if cfg.moe else "stack"
        yield kind, count, p[name]["stack"]


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots_saveable":
        policy = jax.checkpoint_policies.dots_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(p: Params, tokens: jax.Array, rt: Runtime, table: jax.Array,
            prefix_embeds: Optional[jax.Array] = None):
    """tokens: [B, S] -> (hidden [B, S(+P), d], table, aux_total).

    prefix_embeds: [B, P, d] multimodal prefix (vlm) prepended to the text."""
    cfg = rt.cfg
    x = embed(p, tokens, rt)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    aux_total = jnp.float32(0.0)

    for kind, count, stack in _stacks(p, cfg):
        def body(carry, layer_p, kind=kind):
            x, table, aux = carry
            x, table, aux_i, _ = decoder_layer(layer_p, x, rt, table,
                                               positions, kind)
            return (x, table, aux + aux_i), None

        body = _remat(body, cfg)
        if cfg.scan_layers:
            with scan_multiplier(count):
                (x, table, aux_total), _ = jax.lax.scan(
                    body, (x, table, aux_total), stack)
        else:
            for i in range(count):
                layer_p = jax.tree.map(lambda a: a[i], stack)
                (x, table, aux_total), _ = body((x, table, aux_total), layer_p)

    x = norm(p["final_norm"], x, rt)
    return x, table, aux_total


def loss_fn(p: Params, batch: Dict[str, jax.Array], rt: Runtime,
            table: jax.Array):
    """batch: tokens [B,S], labels [B,S], mask [B,S] (+ patches for vlm)."""
    cfg = rt.cfg
    prefix = None
    if cfg.family == "vlm":
        prefix = _project_patches(p, batch["patches"], rt)
    x, table, aux = forward(p, batch["tokens"], rt, table, prefix)
    if prefix is not None:
        x = x[:, prefix.shape[1]:]          # loss on text positions only
    logits = lm_head(p, x, rt)
    loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
    metrics = {"loss": loss, "aux_loss": aux,
               "tokens": jnp.sum(batch.get("mask", jnp.ones_like(
                   batch["labels"]))).astype(jnp.float32)}
    return loss + aux, (metrics, table)


# --------------------------------------------------------------- serving ----
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None
               ) -> Params:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    return init_kv_cache(cfg, batch, max_len, cfg.n_layers, dtype)


def _split_cache(cache: Params, boundaries) -> Tuple[Params, ...]:
    """Split the [L, ...] stacked cache into per-stack segments."""
    outs = []
    start = 0
    for count in boundaries:
        outs.append(jax.tree.map(lambda a: a[start:start + count], cache))
        start += count
    return tuple(outs)


def forward_chunk(p: Params, tokens: jax.Array, rt: Runtime, table: jax.Array,
                  cache: Params, pos: jax.Array,
                  valid: Optional[jax.Array] = None,
                  prefix_embeds: Optional[jax.Array] = None,
                  block_table: Optional[jax.Array] = None):
    """THE serving entry point: write a T-token chunk at per-slot offsets.

    tokens: [B, T]; pos: [B] int32 per-slot cache depths (scalar
    broadcasts); valid: [B] tokens of the chunk that are real (None = T;
    bucket-padded chunks mask the pad — pad K/V rows are written past the
    frontier but the NEXT chunk overwrites them and no query ever attends
    them).  block_table: [B, NB] int32 — when given, `cache` is the paged
    arena ([L, P, Hkv, page_size, h]) and every layer writes/reads through
    the SAME per-slot table (one table per slot, shared across layers),
    offset to its own pages (layer l's page v is l*P + v).
    Returns (last-valid-token logits [B, V], new stacked cache, table).

    Prefill and decode are this operation at different widths: pos = 0,
    T = prompt length is bulk prefill; T = 1 is the pooled decode tick;
    anything between is a mid-prompt prefill chunk.  Every batch row
    advances independently — rope angles, row-range cache scatters and
    offset-causal masks are all per-row — so one compiled call serves
    slots at arbitrary mixed depths."""
    x = embed(p, tokens, rt)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, T = x.shape[:2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos[:, None] + jnp.arange(T)[None, :]   # [B, T] per-row rope
    if block_table is not None:
        x, table, new_cache = _paged_layers(p, x, rt, table, cache, positions,
                                            pos, block_table)
    else:
        x, table, new_cache = _dense_layers(p, x, rt, table, cache, positions,
                                            pos)
    x = norm(p["final_norm"], x, rt)
    logits = lm_head(p, last_valid(x, valid), rt)[:, 0]
    return logits, new_cache, table


def _dense_layers(p: Params, x: jax.Array, rt: Runtime, table: jax.Array,
                  cache: Params, positions: jax.Array, pos: jax.Array):
    """Per-row caches: each stack scans its [count, ...] cache segment
    with its params and restacks the layers' new caches."""
    new_segs = []
    counts = [c for _, c in _layer_kinds(rt.cfg)]
    for (kind, count, stack), seg in zip(_stacks(p, rt.cfg),
                                         _split_cache(cache, counts)):
        def body(carry, inp, kind=kind):
            x, table = carry
            layer_p, layer_cache = inp
            x, table, _, new_cache = decoder_layer(
                layer_p, x, rt, table, positions, kind,
                cache=layer_cache, pos=pos)
            return (x, table), new_cache

        with scan_multiplier(count):
            (x, table), new_seg = jax.lax.scan(body, (x, table), (stack, seg))
        new_segs.append(new_seg)
    new_cache = jax.tree.map(
        lambda *segs: jnp.concatenate(segs, 0), *new_segs) \
        if len(new_segs) > 1 else new_segs[0]
    return x, table, new_cache


def _paged_layers(p: Params, x: jax.Array, rt: Runtime, table: jax.Array,
                  arena: Params, positions: jax.Array, pos: jax.Array,
                  block_table: jax.Array):
    """The page arena of every layer rides whole in the scan carry; each
    stack scans (params, layer index) from its own first layer, and
    layer l writes and reads its pages at block_table + l*P of the flat
    arena (layers.layer_pages).  No layer is sliced out of the arena or
    restacked, so the scatter updates the donated arena in place."""
    first = 0
    for kind, count, stack in _stacks(p, rt.cfg):
        def body(carry, inp, kind=kind):
            x, table, arena = carry
            layer_p, layer = inp
            x, table, _, arena = decoder_layer(
                layer_p, x, rt, table, positions, kind, cache=arena,
                pos=pos, block_table=block_table, layer=layer)
            return (x, table, arena), None

        layers = first + jnp.arange(count, dtype=jnp.int32)
        with scan_multiplier(count):
            (x, table, arena), _ = jax.lax.scan(body, (x, table, arena),
                                                (stack, layers))
        first += count
    return x, table, arena


def prefill(p: Params, tokens: jax.Array, rt: Runtime, table: jax.Array,
            cache: Params, prefix_embeds: Optional[jax.Array] = None):
    """Bulk prefill = forward_chunk at offset 0 with T = prompt length."""
    zero = jnp.zeros((tokens.shape[0],), jnp.int32)
    return forward_chunk(p, tokens, rt, table, cache, zero,
                         prefix_embeds=prefix_embeds)


def decode_step(p: Params, token: jax.Array, rt: Runtime, table: jax.Array,
                cache: Params, pos: jax.Array):
    """Pooled decode = forward_chunk at width T = 1.  token: [B]."""
    return forward_chunk(p, token[:, None], rt, table, cache, pos)


# ------------------------------------------------------- paged serving ----
def init_paged_cache(cfg: ModelConfig, pages: int, page_size: int, dtype=None
                     ) -> Params:
    """Page-arena KV cache: the per-slot batch dim of init_cache becomes
    the PAGE dim ([L, P, Hkv, page_size, h] / MLA [L, P, page_size, r]).
    Ownership lives outside: the engine's block tables map (slot,
    virtual page) -> arena page, so a 30-token slot holds one page and a
    full-context one holds max_seq_len / page_size — memory follows the
    request, not the worst case.  Page 0 is reserved scratch (in every
    layer: layer l's page v is page l*P + v of the flat arena)."""
    return init_cache(cfg, pages, page_size, dtype)


def forward_chunk_paged(p: Params, tokens: jax.Array, rt: Runtime,
                        table: jax.Array, cache: Params, pos: jax.Array,
                        block_table: jax.Array,
                        valid: Optional[jax.Array] = None,
                        prefix_embeds: Optional[jax.Array] = None):
    """forward_chunk against the page arena — same math, block-table
    indirection for every cache write and read."""
    return forward_chunk(p, tokens, rt, table, cache, pos, valid=valid,
                         prefix_embeds=prefix_embeds,
                         block_table=block_table)


def decode_step_paged(p: Params, token: jax.Array, rt: Runtime,
                      table: jax.Array, cache: Params, pos: jax.Array,
                      block_table: jax.Array):
    """Pooled paged decode = forward_chunk_paged at width T = 1."""
    return forward_chunk_paged(p, token[:, None], rt, table, cache, pos,
                               block_table)


# -------------------------------------------------------------- vlm stub ----
def init_frontend(key, cfg: ModelConfig) -> Params:
    """Projection from precomputed frontend embeddings into d_model."""
    from .layers import _init, pdtype
    return {"frontend": {"w": _init(key, (cfg.frontend_dim, cfg.d_model),
                                    pdtype(cfg))}}


def _project_patches(p: Params, patches: jax.Array, rt: Runtime) -> jax.Array:
    from .layers import linear
    with jax.named_scope("embed"):
        x = linear(p["frontend"]["w"], patches.astype(rt.cdtype))
        return shard(x, "batch", "seq", None)


def declare_fold_slots(spec: DeviceFoldSpec, cfg: ModelConfig) -> None:
    if cfg.moe:
        moe_lib.declare_moe_slots(spec, cfg)
    spec.declare("app", "loss", "train_step", "count")
