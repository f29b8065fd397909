"""Ambient mesh + logical-axis environment for sharding constraints.

Model code never mentions concrete meshes; it calls `shard(x, *logical_axes)`
with logical names ('batch', 'seq', 'model', 'expert', ...). The launch layer
installs a concrete mesh + a logical->mesh translation once per run; on plain
CPU tests nothing is installed and `shard` is a no-op — the same model code
runs everywhere.

Logical axes:
  batch    data-parallel batch dim      -> ('pod', 'data') when present
  seq      sequence (context/SP dim)    -> 'data' for long-decode CP, or None
  model    tensor-parallel dim          -> 'model'
  expert   MoE expert dim               -> 'model' (EP shares the TP axis)
  kv_seq   KV-cache sequence dim        -> 'model' when heads unshardable
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

#: default logical->mesh translation; tuple = axis composition
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),
    "model": ("model",),
    "expert": ("model",),
    "kv_seq": (),
    "vocab": ("model",),
}


def set_runtime_mesh(mesh: Optional[Mesh],
                     rules: Optional[Dict[str, Tuple[str, ...]]] = None) -> None:
    _state.mesh = mesh
    _state.rules = dict(DEFAULT_RULES, **(rules or {}))


def get_runtime_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def get_rules() -> Dict[str, Tuple[str, ...]]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextmanager
def runtime_mesh(mesh: Optional[Mesh],
                 rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    prev_mesh = get_runtime_mesh()
    prev_rules = getattr(_state, "rules", None)
    set_runtime_mesh(mesh, rules)
    try:
        yield
    finally:
        _state.mesh = prev_mesh
        if prev_rules is not None:
            _state.rules = prev_rules


def resolve_spec(*logical_axes: Optional[str]) -> P:
    """Translate logical axis names to a PartitionSpec under current rules,
    dropping mesh axes that do not exist in the installed mesh."""
    mesh = get_runtime_mesh()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    rules = get_rules()
    parts = []
    for ax in logical_axes:
        if ax is None:
            parts.append(None)
            continue
        mapped = tuple(m for m in rules.get(ax, ()) if m in mesh_axes)
        if len(mapped) == 0:
            parts.append(None)
        elif len(mapped) == 1:
            parts.append(mapped[0])
        else:
            parts.append(mapped)
    return P(*parts)


def shard(x, *logical_axes: Optional[str]):
    """with_sharding_constraint against the ambient mesh; no-op without one
    and inside a shard_map body (values there are already per device)."""
    mesh = get_runtime_mesh()
    if mesh is None or in_manual_region():
        return x
    spec = resolve_spec(*logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_dims(x, dim_axes: Dict[int, str]):
    """with_sharding_constraint mapping dim index -> logical axis, applying
    an axis ONLY when the dim size divides the mesh extent (GQA heads < TP,
    batch=1 long-decode, ... stay replicated instead of unevenly sharded).

    Use inside kernel-pattern scan bodies/carries: XLA's SPMD partitioner
    picks replicated for unconstrained while-loop carries and then re-gathers
    operands EVERY iteration (measured: a 16 GB all-gather per kv-block on
    deepseek MLA train — EXPERIMENTS.md §Perf)."""
    mesh = get_runtime_mesh()
    if mesh is None or in_manual_region():
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, dims_spec(x.shape, dim_axes)))


def dims_spec(shape: Tuple[int, ...], dim_axes: Dict[int, str]) -> P:
    """The PartitionSpec `shard_dims` applies: each listed dim gets its
    logical axis's mesh axes where the dim size divides their extent,
    and stays replicated otherwise.  Requires an installed mesh."""
    mesh = get_runtime_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    rules = get_rules()
    parts: list = [None] * len(shape)
    used: set = set()
    for dim, logical in dim_axes.items():
        mapped = tuple(m for m in rules.get(logical, (logical,))
                       if m in sizes and m not in used)
        extent = 1
        for m in mapped:
            extent *= sizes[m]
        if mapped and extent > 1 and shape[dim] % extent == 0:
            parts[dim] = mapped[0] if len(mapped) == 1 else mapped
            used.update(mapped)
    return P(*parts)


def in_manual_region() -> bool:
    """True while tracing a shard_map body, where values are per-device."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def named_sharding(*logical_axes: Optional[str]) -> Optional[NamedSharding]:
    mesh = get_runtime_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve_spec(*logical_axes))


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 without a mesh)."""
    mesh = get_runtime_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for m in get_rules().get(logical, ()):
        n *= sizes.get(m, 1)
    return n
