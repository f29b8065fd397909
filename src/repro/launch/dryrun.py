import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

The lines above run BEFORE any other import (jax locks the device count
at first init).  The dry-run runs on 512 virtual CPU devices and pins the
CPU platform, so on a host with a chip neither it nor the children
`dryrun_all.py` starts ever takes the chip.  Do NOT import this module
from tests — run it as
`python -m repro.launch.dryrun --arch <id> --shape <name> [--multi-pod]`.

Per cell, the dry-run records to artifacts/dryrun/<cell>.json:
  * memory_analysis()  — bytes/device: proves the cell fits 16 GB HBM
  * cost_analysis()    — HLO FLOPs + bytes accessed (per-device, post-SPMD)
  * the collective schedule (kind, scope, mesh axis, wire bytes) parsed from
    the optimized HLO by the XFA static layer (core.hlo_flows)
  * the three roofline terms in seconds + the dominant term
  * MODEL_FLOPS = 6·N·D (6·N_active·D for MoE) and the useful-compute ratio
"""

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
import time      # noqa: E402

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from repro.configs import SHAPES, get_config           # noqa: E402
from repro.configs.base import TrainConfig             # noqa: E402
from repro.core.device_fold import STATIC_COSTS        # noqa: E402
from repro.core.hlo_analysis import (analyze_module,   # noqa: E402
                                     xla_cost_analysis)
from repro.core.session import KNOWN_COMPONENTS        # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW,         # noqa: E402
                               PEAK_FLOPS_BF16, make_production_mesh,
                               mesh_axis_sizes)
from repro.launch.specs import build_cell, cell_is_applicable  # noqa: E402
from repro.parallel.axes import runtime_mesh           # noqa: E402


#: --dp-only: small models should not be tensor-parallel — fold the model
#: axis into data parallelism (params replicated, 256-way DP, ZeRO-1 state)
DP_ONLY_RULES = {"batch": ("pod", "data", "model"), "model": (),
                 "expert": (), "vocab": (), "kv_seq": ()}


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = "artifacts/dryrun",
             overrides: dict | None = None,
             tcfg: TrainConfig | None = None,
             tag: str = "", rules: dict | None = None) -> dict:
    cfg = get_config(arch)
    import dataclasses
    # dry-run default: full remat (save only layer inputs). dots_saveable
    # would stack every chunked-attention dot residual per layer — measured
    # +40 GiB/device on tinyllama train_4k (EXPERIMENTS.md §Perf).
    cfg = dataclasses.replace(cfg, remat="full")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    if not cell_is_applicable(cfg, shape):
        return {"cell": f"{cfg.name}:{shape.name}", "skipped": True,
                "reason": "long_500k needs sub-quadratic attention"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = mesh_axis_sizes(mesh)
    n_chips = mesh.devices.size
    t0 = time.time()
    if rules is None and getattr(cfg, "prefer_dp_only", False) \
            and shape.kind == "train" \
            and shape.global_batch % n_chips == 0:
        # pure DP needs batch >= devices; on the 512-chip mesh batch 256
        # keeps TP (the pod axis still composes with data)
        rules = DP_ONLY_RULES

    with runtime_mesh(mesh, rules):
        cell = build_cell(cfg, shape, mesh, tcfg=tcfg)
        # one clean abstract trace for the XFA static layer: exact analytic
        # kernel FLOPs/HBM-bytes with scan multiplicity (the trace IS the
        # count — no runtime representation needed)
        STATIC_COSTS.reset()
        jax.eval_shape(cell.fn, *cell.args)
        static = STATIC_COSTS.as_folded()
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    kernel_bytes_global = sum(e.metrics.get("bytes", 0.0)
                              for e in static.edges.values())
    kernel_flops_global = sum(e.metrics.get("flops", 0.0)
                              for e in static.edges.values())

    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = xla_cost_analysis(compiled)
    hlo = compiled.as_text()
    # loop-aware static analysis (core.hlo_analysis): XLA's cost_analysis
    # counts while bodies ONCE; scan-over-layers models need trip-count-
    # aware totals for FLOPs / bytes / collective wire traffic.
    mc = analyze_module(hlo, KNOWN_COMPONENTS, sizes)

    flops_dev = float(mc.flops)
    # memory model: loop-aware HLO buffer writes OUTSIDE kernel loops (VMEM-
    # internal tiles excluded) + the kernels' analytic HBM traffic (XFA
    # static layer), which the Pallas kernels touch exactly once
    bytes_dev = float(mc.io_bytes) + kernel_bytes_global / n_chips
    wire_dev = float(mc.wire_bytes)

    t_compute = flops_dev / PEAK_FLOPS_BF16
    t_memory = bytes_dev / HBM_BW
    t_collective = wire_dev / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)

    # useful-FLOPs ratio: 6ND for train, 2·N_active·tokens for serving steps
    n_act = cfg.n_active_params()
    if shape.kind == "train":
        model_flops = 6.0 * n_act * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_act * shape.global_batch * shape.seq_len
    else:
        model_flops = 2.0 * n_act * shape.global_batch
    model_flops_dev = model_flops / n_chips
    ratio = model_flops_dev / flops_dev if flops_dev else 0.0
    bound = max(terms.values())
    roofline_fraction = (model_flops_dev / PEAK_FLOPS_BF16) / bound \
        if bound else 0.0

    record = {
        "cell": f"{cfg.name}:{shape.name}",
        "tag": tag,
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names)},
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "bytes_per_device": getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "cost_analysis": {
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "hlo_io_bytes_per_device": float(mc.io_bytes),
            "kernel_bytes_per_device": kernel_bytes_global / n_chips,
            "static_kernel_flops_per_device": kernel_flops_global / n_chips,
            "xla_flops_body_once": float(cost.get("flops", 0.0)),
            "xla_bytes_body_once": float(cost.get("bytes accessed", 0.0)),
            "analyzer_flops_body_once": mc.flops_body_once,
        },
        "collectives": {
            "wire_bytes_per_device": wire_dev,
            "by_kind": mc.by_kind_wire,
            "by_axis": mc.by_axis_wire,
            "by_component": mc.by_component_wire,
            "count": mc.n_collectives,
            "schedule_head": mc.collectives[:40],
        },
        "roofline": {
            **terms,
            "dominant": dominant,
            "model_flops_global": model_flops,
            "useful_flops_ratio": ratio,
            "roofline_fraction": roofline_fraction,
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = "multipod" if multi_pod else "pod"
    tagpart = f"_{tag}" if tag else ""
    fname = f"{arch}_{shape_name}_{suffix}{tagpart}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="",
                    help="comma k=v model-config overrides (perf loop)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--deferred-grads", action="store_true")
    ap.add_argument("--dp-only", action="store_true")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override.split(","):
        if not kv:
            continue
        k, v = kv.split("=")
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v == "True":
            v = True
        if v == "False":
            v = False
        overrides[k] = v

    tcfg = TrainConfig(microbatches=args.microbatches,
                       zero1=not args.no_zero1,
                       grad_compression=args.grad_compression,
                       deferred_grad_reduce=args.deferred_grads)
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   overrides or None, tcfg, args.tag,
                   rules=DP_ONLY_RULES if args.dp_only else None)
    if rec.get("skipped"):
        print(f"SKIP {rec['cell']}: {rec['reason']}")
        return 0
    print(f"OK {rec['cell']} mesh={rec['mesh']['shape']} "
          f"compile={rec['compile_s']}s")
    ma = rec["memory_analysis"]
    print(f"  memory/device: args={ma['argument_bytes']/2**30:.2f}GiB "
          f"temp={ma['temp_bytes']/2**30:.2f}GiB "
          f"peak={ma['peak_bytes']/2**30:.2f}GiB")
    ca = rec["cost_analysis"]
    ro = rec["roofline"]
    print(f"  flops/dev={ca['flops_per_device']:.3e} "
          f"bytes/dev={ca['bytes_per_device']:.3e} "
          f"wire/dev={rec['collectives']['wire_bytes_per_device']:.3e}")
    print(f"  roofline: compute={ro['compute_s']*1e3:.2f}ms "
          f"memory={ro['memory_s']*1e3:.2f}ms "
          f"collective={ro['collective_s']*1e3:.2f}ms "
          f"dominant={ro['dominant']} "
          f"useful_ratio={ro['useful_flops_ratio']:.2f} "
          f"roofline_frac={ro['roofline_fraction']:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
