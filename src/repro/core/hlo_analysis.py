"""Loop-aware static analysis of optimized HLO — the roofline's data source.

XLA's HloCostAnalysis (and compiled.cost_analysis()) counts each while-loop
BODY ONCE, so for scan-over-layers models it under-reports FLOPs, bytes and
collective traffic by the trip count (verified empirically in
tests/test_hlo_analysis.py). This module re-analyzes the optimized HLO text
with loop multiplicity:

  1. split the module into computations, building a per-computation symbol
     table (%name -> shape; operands carry no inline types in optimized HLO),
  2. find every `while`, read its trip count from the condition computation
     (jax scans lower to `compare(iv, constant(N))`),
  3. propagate multipliers through the call graph (body/condition/calls/
     to_apply/branches — nested scans multiply),
  4. per computation count
       * dot FLOPs:   2 · prod(result dims) · prod(lhs contracting dims)
       * op IO bytes: result + operand bytes of buffer-level ops
       * collective wire bytes (ring model; replica-group axis attribution)
  5. total = Σ per-computation cost × multiplier.

This is the Scaler move transplanted: read the binary instead of
instrumenting the program — zero runtime overhead, exact static structure.
The paper reads .rela.plt; we read the HLO module.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .hlo_flows import (COLLECTIVE_KINDS, DTYPE_BYTES, _GROUPS_EXPLICIT_RE,
                        _GROUPS_IOTA_RE, _OPNAME_RE, _resolve_axis,
                        _resolve_component)

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{$")
_DEF = re.compile(r"^(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+)$")
_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
#: model scopes whose inner loops are Pallas-kernel stand-ins — their loop
#: bodies' buffers live in VMEM on TPU, not HBM; their HBM traffic is
#: accounted analytically by the XFA static layer (kernels/ops annotate_cost)
KERNEL_SCOPES = ("attention", "norm", "ssm", "mlstm", "slstm")

_COND = re.compile(r"condition=%?([\w\.\-]+)")
_BODY = re.compile(r"body=%?([\w\.\-]+)")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONST_INT = re.compile(r"constant\((\d+)\)")
_LHS_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS = re.compile(r"%([\w\.\-]+)")

# buffer-level ops whose IO approximates HBM traffic in optimized HLO.
# Raw elementwise ops are EXCLUDED: on TPU they fuse; the CPU-backend HLO we
# analyze wraps them in kLoop `fusion` ops whose boundary IO we do count.
_BYTES_OPS = {
    "fusion", "dot", "custom-call", "copy", "reduce", "scatter",
    "gather", "dynamic-slice", "dynamic-update-slice", "pad", "concatenate",
    "slice", "transpose", "select-and-scatter", "sort",
    "convolution", "reverse", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute",
}


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """compiled.cost_analysis(), or {} where the backend reports none."""
    return compiled.cost_analysis() or {}


def _shape_info(type_str: str) -> Tuple[int, List[List[int]]]:
    """(total bytes, list of dim-lists) for a (possibly tuple) type string."""
    total = 0
    dims_list = []
    for m in _SHAPE.finditer(type_str):
        dtype, dims_s = m.group(1), m.group(2)
        dims = [int(d) for d in dims_s.split(",") if d]
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES.get(dtype, 4)
        dims_list.append(dims)
    return total, dims_list


def _split_def(rhs: str) -> Tuple[str, str, str, str]:
    """rhs of '=' -> (result_type_str, op_kind, operand_str, attr_str)."""
    # op kind is the first lowercase word followed by '(' after the type
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", rhs)
    if not m:
        return rhs, "", "", ""
    kind = m.group(1)
    result_part = rhs[: m.start()]
    rest = rhs[m.end():]
    depth = 1
    i = 0
    while i < len(rest) and depth:
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
        i += 1
    return result_part, kind, rest[: i - 1], rest[i:]


@dataclass
class CollectiveOp:
    kind: str
    wire_bytes: float
    axis: str
    component: str
    comp_name: str
    bytes_moved: float


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    symbols: Dict[str, Tuple[int, List[List[int]]]] = field(default_factory=dict)
    while_refs: List[Tuple[str, str]] = field(default_factory=list)
    call_refs: List[str] = field(default_factory=list)
    fusion_refs: List[str] = field(default_factory=list)
    kernel_bodies: set = field(default_factory=set)
    cond_consts: List[int] = field(default_factory=list)
    flops: float = 0.0
    io_bytes: float = 0.0
    collectives: List[CollectiveOp] = field(default_factory=list)
    fusion_only: bool = False          # set by compute_multipliers
    vmem_internal: bool = False        # inside a kernel-scope while loop


def parse_module(text: str, known_components: Sequence[str] = (),
                 mesh_axes: Optional[Dict[str, int]] = None
                 ) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    mesh_axes = mesh_axes or {}
    for raw in text.splitlines():
        line = raw.strip()
        if cur is None:
            h = _COMP_HEADER.match(line)
            if h:
                cur = Computation(name=h.group(2), is_entry=bool(h.group(1)))
                comps[cur.name] = cur
            continue
        if line == "}":
            cur = None
            continue
        d = _DEF.match(line)
        if not d:
            continue
        name, rhs = d.group(1), d.group(2)
        result_part, kind, operand_str, attr_str = _split_def(rhs)
        res_bytes, res_dims = _shape_info(result_part)
        cur.symbols[name] = (res_bytes, res_dims)

        if kind == "while":
            c = _COND.search(attr_str)
            b = _BODY.search(attr_str)
            if c and b:
                om = _OPNAME_RE.search(raw)
                scope = om.group(1) if om else ""
                kernel = any(f"/{ks}/" in scope or scope.endswith(f"/{ks}")
                             for ks in KERNEL_SCOPES)
                cur.while_refs.append((c.group(1), b.group(1)))
                if kernel:
                    cur.kernel_bodies.add(b.group(1))
                    cur.kernel_bodies.add(c.group(1))
            continue
        for cm in _CALLS.finditer(attr_str):
            # fusion-called computations are FUSED: their ops produce no
            # buffers (IO is the fusion op's boundary), but dots inside them
            # are real FLOPs -> track the ref kind.
            if kind == "fusion":
                cur.fusion_refs.append(cm.group(1))
            else:
                cur.call_refs.append(cm.group(1))
        bm = _BRANCHES.search(attr_str)
        if bm:
            cur.call_refs += [n.strip().lstrip("%") for n in
                              bm.group(1).split(",")]
        for im in _CONST_INT.finditer(rhs):
            cur.cond_consts.append(int(im.group(1)))

        operands = _OPERANDS.findall(operand_str)
        op_bytes_list = [cur.symbols.get(o, (0, []))[0] for o in operands]
        op_bytes = sum(op_bytes_list)

        if kind == "dot":
            lhs_dims = cur.symbols.get(operands[0], (0, [[]]))[1]
            lhs_dims = lhs_dims[0] if lhs_dims else []
            result_elems = 1
            for dl in res_dims:
                for dd in dl:
                    result_elems *= dd
            contract = 1
            cm2 = _LHS_CONTRACT.search(attr_str)
            if cm2 and cm2.group(1).strip():
                for idx in cm2.group(1).split(","):
                    i = int(idx)
                    if i < len(lhs_dims):
                        contract *= lhs_dims[i]
            cur.flops += 2.0 * result_elems * contract

        if kind in COLLECTIVE_KINDS or (kind.endswith("-start") and
                                        kind[:-6] in COLLECTIVE_KINDS):
            base = kind[:-6] if kind.endswith("-start") else kind
            group_size, group_stride = 1, 1
            gm = _GROUPS_IOTA_RE.search(attr_str)
            if gm:
                n_groups, g_size = int(gm.group(1)), int(gm.group(2))
                group_size = g_size
                group_stride = n_groups if gm.group(3) else 1
            else:
                gm2 = _GROUPS_EXPLICIT_RE.search(attr_str)
                if gm2:
                    ids = [int(x) for x in
                           gm2.group(1).replace(" ", "").split(",") if x]
                    group_size = len(ids)
                    group_stride = (ids[1] - ids[0]) if len(ids) > 1 else 1
            if base == "collective-permute":
                group_size = 2
            n = max(group_size, 1)
            f = (n - 1) / n if n > 1 else 0.0
            if base == "all-gather":
                moved = res_bytes
                wire = f * res_bytes
            elif base == "reduce-scatter":
                moved = op_bytes
                wire = f * op_bytes
            elif base == "all-reduce":
                moved = op_bytes
                wire = 2.0 * f * op_bytes
            elif base == "all-to-all":
                moved = op_bytes
                wire = f * op_bytes
            else:  # collective-permute
                moved = op_bytes
                wire = float(op_bytes)
            om = _OPNAME_RE.search(raw)
            op_name = om.group(1) if om else ""
            cur.collectives.append(CollectiveOp(
                kind=base, wire_bytes=wire,
                axis=_resolve_axis(group_size, group_stride, mesh_axes)
                if mesh_axes else f"size{group_size}",
                component=_resolve_component(op_name, known_components),
                comp_name=cur.name, bytes_moved=moved))

        if kind in _BYTES_OPS:
            cur.io_bytes += _op_io(kind, name, res_bytes, op_bytes_list)
    return comps


def _op_io(kind: str, op_name: str, res_bytes: int,
           op_bytes_list: List[int]) -> float:
    """HBM traffic model for one buffer-level op: 2 x result bytes
    (buffer written once + read ~once by its consumer).

    Counting full operand bytes per use would bill a buffer once per
    consumer and blow up 10-50x on CPU-backend HLO, whose fusion granularity
    is much finer than TPU's (measured on tinyllama train_4k — EXPERIMENTS.md
    §Perf iteration 0). Counting writes is fusion-invariant: every buffer
    that exists is written exactly once. Update-like ops alias their big
    operand in place and touch only the updated region (~ the non-buffer
    operands)."""
    total = sum(op_bytes_list)
    largest = max(op_bytes_list, default=0)
    tag = op_name if kind == "fusion" else kind
    if "dynamic-update-slice" in tag or "scatter" in tag:
        return 2.0 * (total - largest)
    return 2.0 * res_bytes


def trip_count(cond: Computation) -> int:
    """jax scan conditions compare the induction var with constant(N)."""
    return max(cond.cond_consts) if cond.cond_consts else 1


def compute_multipliers(comps: Dict[str, Computation]) -> Dict[str, float]:
    mult: Dict[str, float] = {name: 0.0 for name in comps}
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {name: 1.0 for name in comps}
    mult[entry.name] = 1.0
    for _ in range(len(comps) + 2):
        changed = False
        for c in comps.values():
            m = mult.get(c.name, 0.0)
            if m == 0.0:
                continue
            for cond_name, body_name in c.while_refs:
                trips = trip_count(comps[cond_name]) \
                    if cond_name in comps else 1
                for target, factor in ((body_name, trips),
                                       (cond_name, trips + 1)):
                    if target in comps and mult[target] < m * factor:
                        mult[target] = m * factor
                        changed = True
            for name in c.call_refs + c.fusion_refs:
                if name in comps and mult[name] < m:
                    mult[name] = m
                    changed = True
        if not changed:
            break
    # mark computations reachable ONLY through fusion calls: FLOPs count,
    # buffer IO does not (the fusion boundary already accounted it)
    control_reach = set()
    entry2 = next((c for c in comps.values() if c.is_entry), None)
    frontier = [entry2.name] if entry2 else []
    while frontier:
        name = frontier.pop()
        if name in control_reach or name not in comps:
            continue
        control_reach.add(name)
        c = comps[name]
        for cond_name, body_name in c.while_refs:
            frontier += [cond_name, body_name]
        frontier += c.call_refs
    for name, c in comps.items():
        c.fusion_only = name not in control_reach
    # mark kernel-internal (VMEM) subtrees: bodies of while loops under a
    # kernel named_scope, and everything they reach
    kernel_roots = set()
    for c in comps.values():
        kernel_roots |= c.kernel_bodies
    frontier = list(kernel_roots)
    internal = set()
    while frontier:
        name = frontier.pop()
        if name in internal or name not in comps:
            continue
        internal.add(name)
        c = comps[name]
        for cond_name, body_name in c.while_refs:
            frontier += [cond_name, body_name]
        frontier += c.call_refs + c.fusion_refs
    for name, c in comps.items():
        c.vmem_internal = name in internal
    for name, v in mult.items():
        if v == 0.0:
            mult[name] = 1.0   # unreached (dead) computations: count once
    return mult


@dataclass
class ModuleCosts:
    flops: float                      # loop-aware dot FLOPs (per device)
    io_bytes: float                   # loop-aware buffer IO bytes (per device)
    wire_bytes: float                 # loop-aware collective wire bytes
    multipliers: Dict[str, float]
    flops_body_once: float
    by_kind_wire: Dict[str, float] = field(default_factory=dict)
    by_axis_wire: Dict[str, float] = field(default_factory=dict)
    by_component_wire: Dict[str, float] = field(default_factory=dict)
    collectives: List[Tuple[str, str, str, float, float]] = \
        field(default_factory=list)   # (kind, component, axis, wire, mult)
    n_collectives: int = 0


def analyze_module(text: str, known_components: Sequence[str] = (),
                   mesh_axes: Optional[Dict[str, int]] = None) -> ModuleCosts:
    comps = parse_module(text, known_components, mesh_axes)
    mult = compute_multipliers(comps)

    flops = sum(c.flops * mult[c.name] for c in comps.values())
    flops_once = sum(c.flops for c in comps.values())
    io_bytes = sum(c.io_bytes * mult[c.name] for c in comps.values()
                   if not (c.fusion_only or c.vmem_internal))

    wire = 0.0
    by_kind: Dict[str, float] = {}
    by_axis: Dict[str, float] = {}
    by_comp: Dict[str, float] = {}
    schedule = []
    n = 0
    for c in comps.values():
        m = mult[c.name]
        for col in c.collectives:
            wb = col.wire_bytes * m
            wire += wb
            n += 1
            by_kind[col.kind] = by_kind.get(col.kind, 0.0) + wb
            by_axis[col.axis] = by_axis.get(col.axis, 0.0) + wb
            by_comp[col.component] = by_comp.get(col.component, 0.0) + wb
            schedule.append((col.kind, col.component, col.axis,
                             col.wire_bytes, m))
    return ModuleCosts(flops=flops, io_bytes=io_bytes, wire_bytes=wire,
                       multipliers=mult, flops_body_once=flops_once,
                       by_kind_wire=by_kind, by_axis_wire=by_axis,
                       by_component_wire=by_comp, collectives=schedule,
                       n_collectives=n)
