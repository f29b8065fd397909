"""The yardstick's arithmetic: percentiles, spreads, interval unions,
FLOP and byte counts, traffic steadiness, weights, configurations."""

import copy
import json
import math
import statistics
from pathlib import Path

import numpy as np
import pytest

from bench import flops, stats, traffic
from bench.spec import Spec, reader

DATA = Path(__file__).resolve().parent / "data"


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile([3.0], 50) == 3.0
    assert stats.percentile([], 50) is None


def test_percentile_counts_missing_as_late():
    xs = [1.0] * 9 + [math.inf]
    assert stats.percentile(xs, 90) == 1.0
    assert stats.percentile(xs + [math.inf], 90) == math.inf


def test_quartile_spread_matches_statistics():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.2]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / med)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 7)]
    assert stats.union_length(iv) == 5
    assert stats.union_length(iv, 1, 6) == 3
    assert stats.gaps(iv, 0, 10) == [(3, 5), (7, 10)]
    assert stats.gaps([], 0, 1) == [(0, 1)]


DIMS = flops.Dims(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2,
                  d_ff=16, vocab=10)


def test_layer_params():
    # q, o: 8x8 each; k, v: 8x4 each; mlp 2 x 8x16
    assert DIMS.layer_matmul_params == 64 + 64 + 32 + 32 + 256


def test_chunk_ctx_sum_is_offset_causal_pairs():
    pairs = sum(pos + 1 for pos in range(5, 5 + 4))   # queries at 5..8
    assert flops.chunk_ctx_sum(5, 4) == pairs


def test_serve_step_flops_by_hand():
    rows = [(0, 3), (7, 1), (4, 0)]
    want = 0.0
    for pos, n in rows[:2]:
        want += 2 * n * 2 * DIMS.layer_matmul_params + 2 * 8 * 10
        want += 2 * 4 * 4 * 2 * sum(pos + t + 1 for t in range(n))
    assert flops.serve_step_flops(DIMS, rows) == want


def test_kernel_costs_by_hand():
    fl, by = flops.decode_attn_cost(DIMS, [3, 5])
    assert fl == 4 * 4 * 2 * 8
    assert by == 2 * 2 * 8 * 2 * 2 + 2 * (2 * 4 * 2 * 2)
    fl, by = flops.chunk_attn_cost(DIMS, [(2, 2)])
    assert fl == 4 * 4 * 2 * (3 + 4)
    assert by == 2 * 2 * 4 * 2 * 2 + 2 * 2 * 4 * 2 * 2
    fl, by = flops.flash_attn_cost(DIMS, 1, 4)
    assert fl == 4 * 4 * 2 * 10
    assert by == 4 * 2 * 2 * (8 + 4) + 4 * 4 * 4


def test_min_time_picks_the_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.min_time((1000.0, 5.0), peaks) == (10.0, "compute")
    assert flops.min_time((10.0, 50.0), peaks) == (5.0, "memory")


def test_train_flops_per_token():
    attn = 2 * 4 * 4 * 2 * (8 + 1) / 2
    want = 3 * (2 * (2 * DIMS.layer_matmul_params + 8 * 10) + attn)
    assert flops.train_flops_per_token(DIMS, 8) == pytest.approx(want)


# Readings of the counts the dense configurations were measured with,
# taken before MoE and MLA were counted: they may not move.
GOLDEN_ROWS = [(0, 512), (100, 1), (3000, 1), (512, 128), (7, 0), (4095, 1)]
GOLDEN_KV = [1, 513, 4095, 64]
GOLDEN = {
    "starcoder2-7b": {
        "serve": 4531008503808.0, "train": 23102668800.0,
        "decode": (86132736.0, 9644032.0), "chunk": (3913445376.0, 28952576.0),
        "flash": (309388640256.0, 337903616)},
    "granite-20b": {
        "serve": 6407981776896.0, "train": 32360583168.0,
        "decode": (114843648.0, 2490880.0), "chunk": (5217927168.0, 20077568.0),
        "flash": (412518187008.0, 414187520)},
    "starcoder2-7b-train": {
        "serve": 566659178496.0, "train": 3057702912.0,
        "decode": (86132736.0, 9644032.0), "chunk": (3913445376.0, 28952576.0),
        "flash": (309388640256.0, 337903616)},
}


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_dense_config_counts_unchanged(config):
    dims = flops.Dims.of(Spec.load().config(config)["program"])
    assert {"serve": flops.serve_step_flops(dims, GOLDEN_ROWS),
            "train": flops.train_flops_per_token(dims, 2048),
            "decode": flops.decode_attn_cost(dims, GOLDEN_KV),
            "chunk": flops.chunk_attn_cost(dims, GOLDEN_ROWS),
            "flash": flops.flash_attn_cost(dims, 8, 2048)} == GOLDEN[config]


# one dense layer, then two MoE layers of 4 experts (top 2, 1 shared) of
# width 6; MLA with r 4, dn 2, dr 2, dv 3 over 2 heads
MLA_MOE = flops.Dims(layers=3, d_model=8, heads=2, kv_heads=2, head_dim=4,
                     d_ff=16, vocab=10, gated=True, n_experts=4, top_k=2,
                     n_shared_experts=1, moe_d_ff=6, first_dense_layers=1,
                     mla=True, kv_lora_rank=4, qk_nope_dim=2, qk_rope_dim=2,
                     v_head_dim=3)


def test_moe_mla_params_by_hand():
    # q 8x(2*4), kv down 8x(4+2), kv up 4x(2*(2+3)), o (2*3)x8
    attn = 64 + 48 + 40 + 48
    assert MLA_MOE.attn_params == attn
    assert MLA_MOE.layer_matmul_params == attn + 3 * 8 * 16
    # router 8x4; 2 routed + 1 shared gated experts of 3 x 8x6
    assert MLA_MOE.moe_layer_params == attn + 32 + 3 * 144
    assert MLA_MOE.matmul_params == (attn + 384) + 2 * (attn + 32 + 432)


def test_moe_mla_serve_step_flops_by_hand():
    # latent form: 2 heads score over r + dr = 6, sum values over r = 4
    pair = 2 * 2 * 6 + 2 * 2 * 4
    want = 0.0
    for n, ctx in ((3, 1 + 2 + 3), (1, 8)):
        want += 2 * n * 1912 + 2 * 8 * 10 + 3 * pair * ctx
    assert flops.serve_step_flops(MLA_MOE, [(0, 3), (7, 1), (4, 0)]) == want


def test_moe_mla_train_flops_by_hand():
    # expanded form: 2 heads score over dn + dr = 4, sum values over dv = 3
    pair = 2 * 2 * 4 + 2 * 2 * 3
    want = 3 * (2 * (1912 + 8 * 10) + 3 * pair * (8 + 1) / 2)
    assert flops.train_flops_per_token(MLA_MOE, 8) == want


def test_mla_kernel_costs_by_hand():
    # one latent row of 6 per position; q 2x6 and o 2x4 in latent space
    fl, by = flops.decode_attn_cost(MLA_MOE, [3, 5])
    assert fl == 40 * 8
    assert by == 8 * 6 * 2 + 2 * (2 * (6 + 4) * 2)
    fl, by = flops.chunk_attn_cost(MLA_MOE, [(2, 2)])
    assert fl == 40 * (3 + 4)
    assert by == 4 * 6 * 2 + 2 * 2 * (6 + 4) * 2
    # expanded heads: q, k of 2x4 and v, o of 2x3 per position
    fl, by = flops.flash_attn_cost(MLA_MOE, 1, 4)
    assert fl == 28 * 10
    assert by == 4 * 2 * (2 * 4 + 2 * 4 + 2 * 3 + 2 * 3) + 2 * 4 * 4


def test_expert_ffn_cost_by_hand():
    # an expert with no tokens reads no weights
    fl, by = flops.expert_ffn_cost(MLA_MOE, [3, 0, 1, 2])
    assert fl == (3 + 1 + 2) * 6 * 8 * 6
    assert by == 3 * (3 * 8 * 6 * 2) + (3 + 1 + 2) * 2 * 8 * 2
    assert flops.expert_ffn_cost(MLA_MOE, [0, 0]) == (0.0, 0.0)


def test_dims_of_reads_the_program_fields():
    program = json.loads((DATA / "configs" / "deepseek-v2-lite.json")
                         .read_text())["program"]
    dims = flops.Dims.of(program)
    assert dims.gated and dims.mla and dims.moe_layers == 5
    # MLA 13.8M, router 0.13M, 8 experts of 3 x 2048x1408: 83.1M
    assert dims.attn_params == 13_762_560
    assert dims.moe_layer_params == 13_762_560 + 131_072 + 69_206_016
    dense = flops.Dims.of({**program, "moe": False, "mla": False,
                           "mlp_gated": False})
    assert (dense.moe_layers, dense.head_dim) == (0, 128)
    assert dense.matmul_params == 6 * (2048 * 128 * 64 + 2 * 2048 * 10944)


MIX = {"rate_per_s": 5.0, "drain_s": 4, "base_seed": 7,
       "prompt": {"median": 50, "sigma": 0.8, "min": 8, "max": 200},
       "output": {"median": 20, "sigma": 0.8, "min": 4, "max": 64}}


def test_open_loop_same_work_every_seed():
    a = traffic.open_loop(MIX, 12345678901, 10.0, 100)
    b = traffic.open_loop(MIX, 3, 10.0, 100)
    wa = [r for r in a if r.in_window]
    assert len(wa) == 50
    assert max(r.due for r in wa) < 10.0 <= min(r.due for r in a
                                                 if not r.in_window)
    # the same gaps and sizes in the same order; only the ids differ
    assert [(r.due, len(r.prompt), r.max_new, r.in_window) for r in a] == \
        [(r.due, len(r.prompt), r.max_new, r.in_window) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))


def test_open_loop_is_a_function_of_the_seed():
    a = traffic.open_loop(MIX, 99, 10.0, 100)
    b = traffic.open_loop(MIX, 99, 10.0, 100)
    assert all((x.prompt == y.prompt).all() and x.due == y.due
               for x, y in zip(a, b))


def test_check_sample_holds_the_longest():
    reqs = traffic.open_loop(MIX, 5, 10.0, 100)
    served = {r.index: np.zeros(r.max_new) for r in reqs}
    pick = traffic.check_sample(reqs, served, 5, 3, 10)
    assert pick[0].max_new == max(r.max_new for r in reqs)
    assert len(pick) >= 3


def test_weights_by_layer_equal_the_stacked_call():
    import jax
    import jax.numpy as jnp
    from bench import weights
    shapes = {"embed": {"table": jax.ShapeDtypeStruct((16, 8), jnp.bfloat16)},
              "stack": {"stack": {"mlp": {"w_up": jax.ShapeDtypeStruct(
                  (3, 8, 12), jnp.bfloat16)}}}}
    p = weights.make_params(shapes, 2 ** 31 + 5)
    key = weights.base_key(2 ** 31 + 5)
    for l in range(3):
        one = weights.leaf(key, "mlp/w_up", l, (8, 12), jnp.bfloat16)
        assert (np.asarray(one) == np.asarray(p["stack"]["stack"]["mlp"]
                                              ["w_up"][l])).all()
    w = np.asarray(p["stack"]["stack"]["mlp"]["w_up"], np.float32)
    assert abs(w.std() * math.sqrt(8) - 1.0) < 0.1
    other = weights.make_params(shapes, 5)
    assert not (np.asarray(other["embed"]["table"])
                == np.asarray(p["embed"]["table"])).all()


# by model_type: program field <- the published config.json key, or a
# function of the file
PUBLISHED = {
    "starcoder2": {
        "d_model": "hidden_size", "n_heads": "num_attention_heads",
        "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
        "n_layers": "num_hidden_layers", "vocab": "vocab_size"},
    "gpt_bigcode": {
        "d_model": "n_embd", "n_heads": "n_head",
        "n_kv_heads": lambda c: 1 if c["multi_query"] else c["n_head"],
        "d_ff": "n_inner", "n_layers": "n_layer", "vocab": "vocab_size"},
    "deepseek_v2": {
        "d_model": "hidden_size", "n_heads": "num_attention_heads",
        "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
        "moe_d_ff": "moe_intermediate_size", "n_experts": "n_routed_experts",
        "top_k": "num_experts_per_tok", "n_shared_experts": "n_shared_experts",
        "first_dense_layers": "first_k_dense_replace",
        "kv_lora_rank": "kv_lora_rank", "qk_nope_dim": "qk_nope_head_dim",
        "qk_rope_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "n_layers": "num_hidden_layers", "vocab": "vocab_size",
        "moe": lambda c: True, "mla": lambda c: True},
}


def agree_with_published(cfg: dict) -> None:
    kind = cfg.get("model_type")
    assert kind in PUBLISHED, f"no published keys known for model_type {kind!r}"
    p = cfg["program"]
    for field, key in PUBLISHED[kind].items():
        want = key(cfg) if callable(key) else cfg[key]
        assert p[field] == want, (kind, field, p[field], want)
    if not p.get("mla"):
        assert p["head_dim"] * p["n_heads"] == p["d_model"]
    assert set(cfg["published"]) == set(cfg["reduced"])


def test_configs_agree_with_their_published_keys():
    spec = Spec.load()
    for c in spec.data["configs"]:
        cfg = spec.config(c["name"])
        agree_with_published(cfg)
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    agree_with_published(json.loads(
        (DATA / "configs" / "deepseek-v2-lite.json").read_text()))


def test_published_keys_catch_each_field():
    cfg = json.loads((DATA / "configs" / "deepseek-v2-lite.json").read_text())
    for field in PUBLISHED["deepseek_v2"]:
        bad = copy.deepcopy(cfg)
        bad["program"][field] = not bad["program"][field] \
            if isinstance(bad["program"][field], bool) \
            else bad["program"][field] + 1
        with pytest.raises(AssertionError, match=field):
            agree_with_published(bad)
    cfg["model_type"] = "xlstm"
    with pytest.raises(AssertionError, match="'xlstm'"):
        agree_with_published(cfg)


def test_every_named_file_exists():
    spec = Spec.load()
    for w in spec.workloads:
        spec.config(w["config"])
        spec.mix(w["traffic"])
        spec.check(w["name"])
    for m in spec.data["end_to_end"] + spec.data["per_layer"]:
        assert callable(reader(m["name"])), m["name"]


def test_split_metric_falls_back_to_its_base_reader():
    # mfu.chat has no file of its own: mfu.py reads it; mfu.train has one
    assert reader("mfu.chat").__module__ == "bench_metric_mfu"
    assert reader("mfu.train").__module__ == "bench_metric_mfu_train"


def test_mix_lengths_fit_the_cache_row():
    spec = Spec.load()
    for w in spec.workloads:
        mix = spec.mix(w["traffic"])
        if mix["kind"] == "train":
            continue
        row = spec.config(w["config"])["serve"]["max_seq_len"]
        assert mix["prompt"]["max"] + mix["output"]["max"] < row
