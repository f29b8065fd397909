"""Compile each cell's programs at their real widths for a described
TPU v5e, with no chip attached, and print what the compiler says about
device memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

Nothing runs: a pass says the programs compile and how many bytes each
needs, not that they are correct or fast.  For a serving cell the
programs are the decode tick at max_batch and the widest prefill group
(prefill_batch rows of prefill_chunk tokens) against the full page
arena; for a training cell, the jitted train step.  The resident bytes
(weights, arena or train state) are counted once; each program adds
its temporaries and any output it does not write in place.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GiB = 2.0 ** 30


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, f"{k}_size_in_bytes") for k in
            ("argument", "output", "alias", "temp")}


def rehearse(cell: dict, spec) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.configs.base import ModelConfig, ServeConfig, TrainConfig
    from repro.kernels import ops
    from repro.models import build_model

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    ops._on_tpu = lambda: True          # lower the Mosaic kernels
    cfg = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    model = build_model(ModelConfig(**cfg["program"]), impl="auto")
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev), t)
    sds = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=dev)
    params = put(jax.eval_shape(model.init, jax.random.key(0)))
    table = put(jax.eval_shape(model.table))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    out = {"cell": cell["name"], "programs": {}}
    if mix["kind"] == "train":
        from repro.optim import adamw
        from repro.runtime.trainer import make_train_step
        state = put(jax.eval_shape(
            lambda p: {"params": p, "opt": adamw.init_state(p)}, params))
        B, S = mix["batch"], mix["seq_len"]
        batch = {"tokens": sds((B, S)), "labels": sds((B, S)),
                 "mask": sds((B, S), jnp.float32)}
        step = jax.jit(make_train_step(model, TrainConfig(**cfg["train"])),
                       donate_argnums=(0,))
        c = step.lower(state, batch, table).compile()
        out["resident"] = nbytes(state)
        out["programs"]["train_step"] = _mem(c)
        out["kernels"] = c.as_text().count("tpu_custom_call")
    else:
        scfg = ServeConfig(**cfg["serve"])
        arena = put(jax.eval_shape(lambda: model.init_paged_cache(
            scfg.max_cache_pages, scfg.page_size)))
        nb = -(-scfg.max_seq_len // scfg.page_size)
        B, b, w = scfg.max_batch, scfg.prefill_batch, scfg.prefill_chunk
        dec = jax.jit(model.decode_step_paged, donate_argnums=(3,))
        c = dec.lower(params, sds((B,)), table, arena, sds((B,)),
                      sds((B, nb))).compile()
        out["programs"]["decode"] = _mem(c)
        chunk = jax.jit(model.forward_chunk_paged, donate_argnums=(3,))
        c2 = chunk.lower(params, sds((b, w)), table, arena, sds((b,)),
                         sds((b, nb)), sds((b,))).compile()
        out["programs"]["chunk"] = _mem(c2)
        out["resident"] = nbytes(params) + nbytes(arena)
        out["weights"] = nbytes(params)
        out["arena"] = nbytes(arena)
        out["kernels"] = (c.as_text().count("tpu_custom_call"),
                          c2.as_text().count("tpu_custom_call"))
    extra = max(m["temp"] + m["output"] - m["alias"]
                for m in out["programs"].values())
    out["peak_estimate"] = out["resident"] + extra
    out["peak_share"] = out["peak_estimate"] / 16e9
    return out


def main(argv) -> int:
    import jax
    from bench.spec import Spec
    jax.config.update("jax_enable_compilation_cache", False)
    spec = Spec.load()
    cells = [c for c in spec.workloads if not argv or c["name"] in argv]
    for cell in cells:
        r = rehearse(cell, spec)
        print(json.dumps(r), flush=True)
        print(f"{r['cell']}: resident {r['resident'] / GiB:.2f} GiB, peak "
              f"estimate {r['peak_estimate'] / GiB:.2f} GiB "
              f"({100 * r['peak_share']:.1f}% of 16 GB)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
