"""Weights from the seed for any stacked tree: the dense configurations'
weights are those of the earlier single-stack rule, bit for bit, and a
MoE + MLA tree is drawn one layer at a time at each matrix's fan-in."""

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.spec import Spec

SEED = 2 ** 31 + 29


def _program_tree(program: dict):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    model = build_model(ModelConfig(**program))
    return jax.eval_shape(model.init, jax.random.key(0))


# -- the rule before stacks other than `stack/stack/` were known ------------
_STACK = "stack/stack/"


def _single_stack_leaf(key, name, layer, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer + 1)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5
    if name.endswith("scale"):
        w = 1.0 + u * 0.2
    else:
        std = 1.0 if name == "embed/table" else shape[0] ** -0.5
        w = u * (std * math.sqrt(12.0))
    return w.astype(dtype)


def _single_stack_params(shapes, seed):
    def build(key):
        def fill(path, sds):
            p = weights._path(path)
            if p.startswith(_STACK):
                return jax.lax.map(
                    lambda l: _single_stack_leaf(key, p[len(_STACK):], l,
                                                 sds.shape[1:], sds.dtype),
                    jnp.arange(sds.shape[0]))
            return _single_stack_leaf(key, p, -1, sds.shape, sds.dtype)
        return jax.tree_util.tree_map_with_path(fill, shapes)
    return jax.jit(build)(weights.base_key(seed))


def _single_stack_names(shapes):
    out = []
    for path, sds in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        p = weights._path(path)
        if p.startswith(_STACK):
            out += [(p[len(_STACK):], l, sds.shape[1:])
                    for l in range(sds.shape[0])]
        else:
            out.append((p, -1, sds.shape))
    return out


@pytest.mark.parametrize("config", ["starcoder2-7b", "granite-20b",
                                    "starcoder2-7b-train"])
def test_dense_configs_keep_their_weights(config):
    program = dict(Spec.load().config(config)["program"])
    kv = max(1, program["n_kv_heads"] // 2)
    program.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=kv,
                   head_dim=16, d_ff=96, vocab=128)
    shapes = _program_tree(program)
    assert weights.leaf_names(shapes) == _single_stack_names(shapes)
    new = weights.make_params(shapes, SEED)
    old = _single_stack_params(shapes, SEED)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                            jax.tree_util.tree_leaves(old)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert (np.asarray(a) == np.asarray(b)).all(), path


# -- a MoE + MLA tree: two stacks, expert matrices [E, d, F] / [E, F, d] -----
@pytest.fixture(scope="module")
def moe_tree():
    import dataclasses
    from repro.configs import deepseek_v2_lite_16b
    cfg = deepseek_v2_lite_16b.smoke()
    shapes = _program_tree(dataclasses.asdict(cfg))
    return cfg, shapes, weights.make_params(shapes, SEED)


def _leaves(tree):
    return {weights._path(p): np.asarray(x, np.float32)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_moe_tree_has_both_stacks(moe_tree):
    _, shapes, _ = moe_tree
    tops = {weights._path(p).split("/")[0]
            for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert {"stack_dense", "stack_moe"} <= tops


def test_moe_matrices_at_their_fan_in(moe_tree):
    cfg, _, params = moe_tree
    # the contracted axis, by what each matrix multiplies
    fan_in = {"moe/w_gate": cfg.d_model, "moe/w_up": cfg.d_model,
              "moe/w_down": cfg.moe_d_ff,
              "moe/shared/w_down": cfg.moe_d_ff * cfg.n_shared_experts,
              "mlp/w_down": cfg.d_ff,
              "attn/wkv_b": cfg.kv_lora_rank,
              "attn/wo": cfg.n_heads * cfg.v_head_dim}
    checked = 0
    for path, w in _leaves(params).items():
        name = path.split("/stack/")[-1]
        if name.endswith("scale") or path == "embed/table":
            continue
        n = fan_in.get(name, cfg.d_model)
        assert abs(w.std() * math.sqrt(n) - 1.0) < 0.1, (path, w.std())
        checked += 1
    assert checked == 19


def test_moe_layers_drawn_alone(moe_tree):
    _, shapes, params = moe_tree
    key = weights.base_key(SEED)
    # jitted, as make_params draws: eager arithmetic may round differently
    draw = jax.jit(weights.leaf, static_argnums=(1, 3, 4))
    names = weights.leaf_names(shapes)
    assert len({(n, l) for n, l, _ in names}) == len(names)
    by_name = {weights.stacked_name(p) or weights._path(p): x
               for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    for name, layer, shape in names:
        x = by_name[name]
        one = draw(key, name, layer, shape, x.dtype)
        got = x if layer < 0 else x[layer]
        assert (np.asarray(one) == np.asarray(got)).all(), (name, layer)


def test_moe_layers_differ(moe_tree):
    _, _, params = moe_tree
    for path, w in _leaves(params).items():
        if "/stack/" not in path:
            continue
        for i in range(w.shape[0]):
            for j in range(i + 1, w.shape[0]):
                assert not (w[i] == w[j]).all(), (path, i, j)


def test_same_name_in_two_stacks_differs():
    sds = jax.ShapeDtypeStruct((2, 16, 8), jnp.float32)
    shapes = {"stack_dense": {"stack": {"attn": {"wq": sds}}},
              "stack_moe": {"stack": {"attn": {"wq": sds}}}}
    p = weights.make_params(shapes, 5)
    a = np.asarray(p["stack_dense"]["stack"]["attn"]["wq"])
    b = np.asarray(p["stack_moe"]["stack"]["attn"]["wq"])
    assert not (a == b).any()
    assert [n for n, _, _ in weights.leaf_names(shapes)] == \
        ["stack_dense/attn/wq"] * 2 + ["stack_moe/attn/wq"] * 2
