"""The yardstick's arithmetic: percentiles, spreads, interval unions,
FLOP and byte counts, traffic steadiness, weights, configurations."""

import math
import statistics

import numpy as np
import pytest

from bench import flops, stats, traffic
from bench.spec import Spec, reader


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


def test_configs_agree_with_their_published_keys():
    spec = Spec.load()
    for c in spec.data["configs"]:
        cfg = spec.config(c["name"])
        p = cfg["program"]
        if cfg["model_type"] == "starcoder2":
            pub = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["intermediate_size"],
                   cfg["num_hidden_layers"], cfg["vocab_size"])
        else:
            pub = (cfg["n_embd"], cfg["n_head"],
                   1 if cfg["multi_query"] else cfg["n_head"],
                   cfg["n_inner"], cfg["n_layer"], cfg["vocab_size"])
        assert pub == (p["d_model"], p["n_heads"], p["n_kv_heads"],
                       p["d_ff"], p["n_layers"], p["vocab"])
        assert p["head_dim"] * p["n_heads"] == p["d_model"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert set(cfg["published"]) == set(cfg["reduced"])


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
