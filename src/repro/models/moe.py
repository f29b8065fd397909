"""Mixture-of-Experts layer: top-k routing, capacity dispatch, EP all-to-all.

Two dispatch modes, equivalence-tested against each other:

  * 'a2a'   (production, default under a mesh): shard_map over the mesh with
            tokens sharded over (pod, data, MODEL) — i.e. the TP axis doubles
            as the expert-parallel axis, DeepSpeed-MoE style. Each rank
            routes its ~T/512 local tokens, sort+scatters them into a
            [E, C_loc, d] capacity buffer, exchanges buffers over the EP axis
            with jax.lax.all_to_all, runs its local expert shard's FFNs, and
            returns them by the inverse all-to-all. The a2a pair appears in
            the dry-run HLO under the 'moe' scope and feeds the roofline
            collective term.
  * 'dense' (no mesh / smoke tests): GShard one-hot dispatch-combine einsum,
            O(T·E·C) masks — fine at test scale, same semantics.

XFA integration: the layer emits *data-dependent* metrics into the device
fold table — per-expert load (tokens routed), dropped-token count, router
aux/z losses — the signals behind the paper's ferret (imbalance) case study,
which no static analysis can see.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.device_fold import DeviceFoldSpec, annotate_cost
from repro.parallel.axes import axis_size, get_runtime_mesh, shard
from jax import shard_map

from .layers import Params, Runtime, _init, linear, pdtype

MOE_CALLER = "decoder"


def declare_moe_slots(spec: DeviceFoldSpec, cfg: ModelConfig) -> None:
    spec.declare(MOE_CALLER, "moe", "dispatch", "expert_load", cfg.n_experts)
    spec.declare(MOE_CALLER, "moe", "dispatch", "dropped_tokens")
    spec.declare(MOE_CALLER, "moe", "router", "aux_loss")
    spec.declare(MOE_CALLER, "moe", "router", "z_loss")
    spec.declare(MOE_CALLER, "moe", "dispatch", "count")


def init_moe(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = pdtype(cfg)
    p: Dict[str, Any] = {
        "router": _init(ks[0], (d, e), dt, scale=d ** -0.5),
        "w_gate": _init(ks[1], (e, d, f), dt),
        "w_up": _init(ks[2], (e, d, f), dt),
        "w_down": _init(ks[3], (e, f, d), dt),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _init(sk[0], (d, fs), dt),
            "w_up": _init(sk[1], (d, fs), dt),
            "w_down": _init(sk[2], (fs, d), dt),
        }
    return {"moe": p}


def _router(router_w, x2: jax.Array, cfg: ModelConfig):
    """x2: [T, d] -> (gates [T,K] f32, idx [T,K], aux, z)."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)      # renormalize
    # Switch-style load-balance aux (over all K choices) + router z-loss
    E = probs.shape[-1]
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [T,K,E]
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)             # [E]
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e) / cfg.top_k
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates, idx, aux, z


def _expert_ffn(w_gate, w_up, w_down, xb: jax.Array) -> jax.Array:
    """xb: [E_loc, C, d] -> [E_loc, C, d]; SwiGLU per expert."""
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(xb.dtype))
    u = jnp.einsum("ecd,edf->ecf", xb, w_up.astype(xb.dtype))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(xb.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(xb.dtype))


def _local_dispatch(x2, idx, E: int, C: int):
    """Sort+scatter capacity dispatch of local tokens.

    x2: [T, d]; idx: [T, K]. Returns (buf [E, C, d], combine meta,
    n_dropped)."""
    T, K = idx.shape
    flat_e = idx.reshape(-1)                                   # [TK]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.cumsum(counts) - counts                      # exclusive
    pos = jnp.arange(T * K) - offsets[sorted_e]                # rank in expert
    keep = pos < C
    n_dropped = jnp.sum(jnp.logical_not(keep))
    tok = order // K                                           # source token
    safe_e = jnp.where(keep, sorted_e, E)                      # OOB -> dropped
    safe_p = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E + 1, C) + x2.shape[1:], x2.dtype)
    buf = buf.at[safe_e, safe_p].set(x2[tok], mode="drop")
    meta = (order, safe_e, safe_p, keep, tok)
    return buf[:E], meta, n_dropped


def _local_combine(yb, meta, gates, T: int):
    """yb: [E, C, d] -> [T, d] f32, weighted by gates [T, K]."""
    order, safe_e, safe_p, keep, tok = meta
    gathered = yb[jnp.minimum(safe_e, yb.shape[0] - 1), safe_p]  # [TK, d]
    g_flat = gates.reshape(-1)[order]
    w = jnp.where(keep, g_flat, 0.0).astype(jnp.float32)
    contrib = gathered.astype(jnp.float32) * w[:, None]
    out = jnp.zeros((T,) + yb.shape[2:], jnp.float32)
    return out.at[tok].add(contrib)


def _moe_local(weights, x2: jax.Array, *, cfg: ModelConfig, C: int,
               ep_axis: str, ep: int, n_token_shards: int):
    """Per-shard MoE body (inside shard_map). x2: [T_loc, d]."""
    router_w, w_gate, w_up, w_down = weights
    T = x2.shape[0]
    E = cfg.n_experts
    e_loc = E // ep
    gates, idx, aux, z = _router(router_w, x2, cfg)
    buf, meta, dropped = _local_dispatch(x2, idx, E, C)
    load = jnp.bincount(idx.reshape(-1), length=E).astype(jnp.float32)

    d = x2.shape[-1]
    bufr = buf.reshape(ep, e_loc, C, d)
    with jax.named_scope("moe_a2a_fwd"):
        recv = jax.lax.all_to_all(bufr, ep_axis, split_axis=0, concat_axis=0)
    xb = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * C, d)
    yb = _expert_ffn(w_gate, w_up, w_down, xb)
    ybr = yb.reshape(e_loc, ep, C, d).transpose(1, 0, 2, 3)
    with jax.named_scope("moe_a2a_bwd"):
        back = jax.lax.all_to_all(ybr, ep_axis, split_axis=0, concat_axis=0)
    yb_local = back.reshape(E, C, d)
    y = _local_combine(yb_local, meta, gates, T)

    # global fold metrics (replicated out_specs): sum/mean over all shards
    axes = tuple(ax for ax in ("pod", "data", "model"))
    load = _psum_over(load, axes)
    dropped = _psum_over(dropped.astype(jnp.float32), axes)
    aux = _psum_over(aux, axes) / n_token_shards
    z = _psum_over(z, axes) / n_token_shards
    return y.astype(x2.dtype), (load, dropped, aux, z)


def _psum_over(v, axes):
    for ax in axes:
        try:
            v = jax.lax.psum(v, ax)
        except NameError:
            pass
    return v


def _moe_dense(mp: Params, x2: jax.Array, cfg: ModelConfig, C: int):
    """GShard one-hot dispatch/combine (reference; O(T·E·C) masks)."""
    T, d = x2.shape
    E, K = cfg.n_experts, cfg.top_k
    gates, idx, aux, z = _router(mp["router"], x2, cfg)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)          # [T,K,E]
    flat = onehot.reshape(T * K, E)
    ranks = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
    in_cap = (ranks < C).astype(jnp.float32) * onehot
    dropped = jnp.sum(onehot) - jnp.sum(in_cap)
    pos_oh = jax.nn.one_hot(
        jnp.sum(ranks * onehot, axis=-1).astype(jnp.int32), C,
        dtype=jnp.float32)                                      # [T,K,C]
    disp = jnp.einsum("tke,tkc->tec", in_cap, pos_oh)           # [T,E,C]
    comb = jnp.einsum("tk,tke,tkc->tec", gates, in_cap, pos_oh)
    xb = jnp.einsum("tec,td->ecd", disp, x2.astype(jnp.float32)
                    ).astype(x2.dtype)
    yb = _expert_ffn(mp["w_gate"], mp["w_up"], mp["w_down"], xb)
    y = jnp.einsum("tec,ecd->td", comb, yb.astype(jnp.float32))
    load = jnp.sum(onehot, axis=(0, 1))
    return y, (load, dropped, aux, z)


def moe(p: Params, x: jax.Array, rt: Runtime, table: jax.Array,
        mode: str = "auto") -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, d] -> (y, updated fold table, aux loss)."""
    cfg = rt.cfg
    mp = p["moe"]
    B, S, d = x.shape
    T = B * S
    mesh = get_runtime_mesh()
    ep = axis_size("expert")
    use_a2a = (mode == "a2a") or (mode == "auto" and mesh is not None
                                  and ep > 1 and cfg.n_experts % ep == 0
                                  and T % (axis_size("batch") * ep) == 0)
    with jax.named_scope("moe"):
        x2 = x.reshape(T, d)
        if use_a2a:
            dp = axis_size("batch")
            n_shards = dp * ep
            t_loc = T // n_shards
            C = max(8, int(t_loc * cfg.top_k / cfg.n_experts
                           * cfg.capacity_factor))
            token_axes = tuple(a for a in ("pod", "data", "model")
                               if a in mesh.axis_names)
            fn = functools.partial(_moe_local, cfg=cfg, C=C, ep_axis="model",
                                   ep=ep, n_token_shards=n_shards)
            fn = shard_map(
                fn, mesh=mesh,
                in_specs=((P(), P("model"), P("model"), P("model")),
                          P(token_axes, None)),
                out_specs=(P(token_axes, None), (P(), P(), P(), P())),
                check_vma=False)
            y2, (load, dropped, aux, z) = fn(
                (mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"]), x2)
        else:
            C = max(4, int(T * cfg.top_k / cfg.n_experts
                           * cfg.capacity_factor))
            y2, (load, dropped, aux, z) = _moe_dense(mp, x2, cfg, C)

        annotate_cost(MOE_CALLER, "moe", "expert_ffn",
                      flops=6.0 * T * cfg.top_k * d * cfg.moe_d_ff)

        y2 = y2.astype(x2.dtype)
        if cfg.n_shared_experts:
            with jax.named_scope("moe_shared"):
                sp = mp["shared"]
                g = jax.nn.silu(linear(sp["w_gate"], x2).astype(jnp.float32))
                u = linear(sp["w_up"], x2).astype(jnp.float32)
                y2 = y2 + linear(sp["w_down"], (g * u).astype(x2.dtype))
                annotate_cost(MOE_CALLER, "moe", "shared_ffn",
                              flops=6.0 * T * d * cfg.moe_d_ff
                              * cfg.n_shared_experts)

        # fold the data-dependent signals (stop_gradient: observability must
        # not perturb training)
        if rt.fold_spec is not None:
            sg = jax.lax.stop_gradient
            emit = rt.fold_spec.emit
            table = emit(table, MOE_CALLER, "moe", "dispatch", "expert_load",
                         sg(load))
            table = emit(table, MOE_CALLER, "moe", "dispatch",
                         "dropped_tokens", sg(dropped.astype(jnp.float32)))
            table = emit(table, MOE_CALLER, "moe", "router", "aux_loss",
                         sg(aux))
            table = emit(table, MOE_CALLER, "moe", "router", "z_loss", sg(z))
            table = emit(table, MOE_CALLER, "moe", "dispatch", "count", 1.0)
        y = y2.reshape(B, S, d)
        aux_total = (cfg.router_aux_weight * aux + 1e-4 * z).astype(jnp.float32)
        return shard(y, "batch", "seq", None), table, aux_total
