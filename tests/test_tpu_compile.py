"""Compile the main-path Pallas kernels for a described TPU v5e, at the
published TinyLlama-1.1B widths (32 q heads, 4 kv heads, head_dim 64,
d_model 2048), with no chip attached.

The TPU compiler runs here against a topology description, so a kernel
the chip's compiler would refuse (block shapes off the (8, 128) tiling,
too much VMEM, a primitive with no Mosaic lowering, a missing VJP) fails
in this file instead of on the chip.  Nothing executes: these tests say
nothing about results or speed.  Every kernel must appear in the
compiled program as a `tpu_custom_call`, i.e. it was lowered by Mosaic,
not run in interpret mode.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import tinyllama_1_1b
from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import rmsnorm as rms
from repro.models import build_model

# TinyLlama-1.1B widths (configs/tinyllama_1_1b.py)
B, HQ, HKV, D, D_MODEL = 4, 32, 4, 64, 2048
SEQ, CACHE, CHUNK, PAGE = 2048, 2048, 512, 64   # CHUNK: ServeConfig default
PAGES = B * CACHE // PAGE + 1          # every row's full context + scratch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip cannot read back a persistent cache entry: keep
    # the cache off while these programs compile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _value_and_grad(fn, argnums):
    """The training pattern: the forward's value is used, so the kernel
    stays in the program beside its backward."""
    def loss(*args):
        out = fn(*args)
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.sum(jnp.sin(o.astype(jnp.float32))) for o in outs)
    return jax.value_and_grad(loss, argnums=argnums)


def _flash(q, k, v):
    return fa.flash_attention(q, k, v, causal=True, interpret=False)


def _rmsnorm(x, w):
    return rms.rmsnorm(x, w, interpret=False)


def _rmsnorm_add(x, r, w):
    return rms.rmsnorm_add(x, r, w, interpret=False)


def _decode(q, k, v, kv_len):
    return dec.decode_attention(q, k, v, kv_len=kv_len, interpret=False)


def _chunk(q, k, v, pos):
    return dec.chunk_attention(q, k, v, pos=pos, interpret=False)


def _decode_paged(q, k, v, bt, kv_len):
    return dec.decode_attention_paged(q, k, v, block_table=bt,
                                      kv_len=kv_len, interpret=False)


def _chunk_paged(q, k, v, bt, pos):
    return dec.chunk_attention_paged(q, k, v, block_table=bt, pos=pos,
                                     interpret=False)


bf16, i32 = jnp.bfloat16, jnp.int32
ATTN = [((B, HQ, SEQ, D), bf16), ((B, HKV, SEQ, D), bf16),
        ((B, HKV, SEQ, D), bf16)]
NORM = [((B * SEQ, D_MODEL), bf16), ((D_MODEL,), bf16)]
NORM_ADD = [NORM[0], NORM[0], NORM[1]]
CACHE_KV = [((B, HKV, CACHE, D), bf16)] * 2
ARENA_KV = [((PAGES, HKV, PAGE, D), bf16)] * 2
TABLE = ((B, CACHE // PAGE), i32)

CASES = {
    "flash_fwd": (_flash, ATTN),
    "flash_grad": (_value_and_grad(_flash, (0, 1, 2)), ATTN),
    "rmsnorm_fwd": (_rmsnorm, NORM),
    "rmsnorm_grad": (_value_and_grad(_rmsnorm, (0, 1)), NORM),
    "rmsnorm_add_fwd": (_rmsnorm_add, NORM_ADD),
    "rmsnorm_add_grad": (_value_and_grad(_rmsnorm_add, (0, 1, 2)), NORM_ADD),
    "decode": (_decode, [((B, HQ, D), bf16), *CACHE_KV, ((B,), i32)]),
    "chunk": (_chunk, [((B, HQ, CHUNK, D), bf16), *CACHE_KV, ((B,), i32)]),
    "decode_paged": (_decode_paged,
                     [((B, HQ, D), bf16), *ARENA_KV, TABLE, ((B,), i32)]),
    "chunk_paged": (_chunk_paged, [((B, HQ, CHUNK, D), bf16), *ARENA_KV,
                                   TABLE, ((B,), i32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# `%name = bf16[d0,d1,...]{layout} opcode(` in the compiled program's text
HLO_OP = re.compile(r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
# Eight rows of the full context: an arena past the chip's 128 MiB of
# VMEM, so a copy of it would count among the program's temporaries (a
# smaller arena's copies can sit in VMEM, which temp bytes leave out)
ARENA_PAGES = 8 * CACHE // PAGE + 1


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_program_updates_arena_in_place(one_chip, monkeypatch,
                                              program):
    """The whole paged decode and prefill-chunk programs of a 4-layer
    model: the arena rides through the layer scan and the KV write lands
    in it in place, so the program needs less scratch than one layer's k
    slice and copies no layer of the arena (nor the whole of it).

    TinyLlama's widths with head_dim 128 (16 q heads, so d_model stays
    2048): the chip's default layout for an array whose minor dim is 64
    puts another dim minor, so a 64-wide arena is re-laid-out on entry
    and exit of every call, whatever the layer scan does."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # lower Mosaic
    L = 4
    cfg = dataclasses.replace(tinyllama_1_1b.CONFIG, n_layers=L,
                              n_heads=HQ // 2, head_dim=2 * D,
                              param_dtype="bfloat16",
                              compute_dtype="bfloat16")
    model = build_model(cfg)
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = put(jax.eval_shape(model.init, jax.random.key(0)))
    table = put(jax.eval_shape(model.table))
    arena = put(jax.eval_shape(
        lambda: model.init_paged_cache(ARENA_PAGES, PAGE)))
    i32s = lambda *shape: jax.ShapeDtypeStruct(shape, i32, sharding=one_chip)
    nb = CACHE // PAGE
    if program == "decode":
        compiled = jax.jit(model.decode_step_paged, donate_argnums=(3,)).lower(
            params, i32s(B), table, arena, i32s(B), i32s(B, nb)).compile()
    else:                       # one row, one page of prompt
        compiled = jax.jit(model.forward_chunk_paged,
                           donate_argnums=(3,)).lower(
            params, i32s(1, PAGE), table, arena, i32s(1), i32s(1, nb),
            i32s(1)).compile()

    k_slice = arena["k"].size // L * arena["k"].dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    arena_sizes = {arena["k"].size // L, arena["k"].size}
    copies = []
    for dtype, dims, op in HLO_OP.findall(compiled.as_text()):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if op in ("copy", "dynamic-update-slice") and n in arena_sizes:
            copies.append(f"{op} {dtype}[{dims}]")
    assert temp < k_slice, (temp, k_slice)
    assert not copies, copies
