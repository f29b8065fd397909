"""Paged KV-cache pool tests: block-table serving cache, memory-gated
admission.

The load-bearing invariant: with `ServeConfig.max_cache_pages > 0` the
engine swaps its contiguous [max_batch, max_seq_len] cache for a page
arena + per-slot block tables, and every serving family must stay
TOKEN-IDENTICAL to both the contiguous engine and per-request sequential
decode — paging changes where cache rows live, never what attention
sees.  Checked bottom-up: `update_cache_pages` against the dense row
scatter, the ref/blocked/Pallas(interpret) paged attention kernels
against their dense oracles (including scratch-page garbage invariance
— page 0 content must carry exactly-zero softmax mass), then
engine-level equivalence at chunk widths {1, 3, bucket-padded,
whole-prompt} for every serving family (recurrent families assert the
documented dense fallback instead).  On top: admission semantics —
page exhaustion back-pressures the FCFS queue head without reordering
or deadlock, impossible requests fail structurally at submit(), pages
recycle across request waves (bounded high-water mark, empty allocator
at drain), the per-tick pad-stash scratch is released, and the
(batch bucket, width) compiled-program bound survives paging.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.kernels import ops, ref
from repro.models import build_model, layers
from repro.serving import PageAllocator, ServingEngine
from test_serving_engine import (SERVING_ARCHS, build, mixed_prompts,
                                 sequential_decode, tiny)

PAGED_ARCHS = ["tinyllama_1_1b", "deepseek_v2_lite_16b"]   # attention KV
DENSE_ARCHS = ["zamba2_2_7b", "xlstm_1_3b"]                # recurrent state
# GQA MoE given a dense first layer: two layer stacks, so the MoE stack's
# scan addresses the arena from layer offset 1
MULTI_STACK_MOE = "phi3_5_moe_42b"


def build_paged(arch, seed=0):
    """`build`, except that MULTI_STACK_MOE gets 3 layers in two stacks."""
    if arch != MULTI_STACK_MOE:
        return build(arch, seed)
    cfg = dataclasses.replace(tiny(arch), n_layers=3, first_dense_layers=1,
                              d_ff=256).validate()
    model = build_model(cfg, impl="ref")
    return cfg, model, model.init(jax.random.key(seed))


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """This module compiles an unusually large program set (paged+dense
    engines at 5 chunk widths, Pallas interpret kernels); on the CPU CI
    box the executables otherwise stay resident for the rest of the
    session and later suite modules crash inside XLA.  Drop them once
    the module is done."""
    yield
    jax.clear_caches()


def scatter_pages(rng, dense_k, page_size, n_pages, bt=None):
    """Shred per-row dense caches [B, Hkv, S, D] into a page arena with a
    randomly permuted block table (page 0 left as scratch).  Pass `bt` to
    reuse a layout (k and v of one cache share one block table)."""
    B, Hkv, S, D = dense_k.shape
    nb = S // page_size
    assert nb * page_size == S
    if bt is None:
        ids = rng.permutation(np.arange(1, n_pages))[:B * nb]
        bt = ids.reshape(B, nb).astype(np.int32)
    else:
        bt = np.asarray(bt)
    pages = np.asarray(rng.normal(size=(n_pages, Hkv, page_size, D)),
                       np.float32)   # garbage everywhere not granted
    for b in range(B):
        for v in range(nb):
            pages[bt[b, v]] = np.asarray(
                dense_k[:, :, v * page_size:(v + 1) * page_size][b])
    return jnp.asarray(pages), jnp.asarray(bt)


class TestUpdateCachePages:
    @pytest.mark.parametrize("seq_axis,shape", [
        (2, (3, 2, 32, 8)),     # GQA KV cache [B, Hkv, S, D]
        (1, (3, 32, 16)),       # MLA latent cache [B, S, dc]
    ])
    def test_matches_dense_row_scatter(self, seq_axis, shape):
        """Scatter-through-indirection == the dense row-range scatter when
        the block table is the identity layout."""
        rng = np.random.default_rng(0)
        B, ps, T = shape[0], 8, 5
        S = shape[seq_axis]
        nb = S // ps
        dense = jnp.asarray(rng.normal(size=shape), jnp.float32)
        src_shape = list(shape)
        src_shape[seq_axis] = T
        src = jnp.asarray(rng.normal(size=src_shape), jnp.float32)
        pos = jnp.asarray([0, 7, 19], jnp.int32)   # straddles page edges
        want = layers.update_cache_rows(dense, src, pos, seq_axis=seq_axis)

        # identity layout: row b's pages are 1+b*nb .. 1+(b+1)*nb-1
        bt = jnp.asarray(1 + np.arange(B * nb).reshape(B, nb), jnp.int32)
        arena_shape = list(shape)
        arena_shape[0] = 1 + B * nb
        arena_shape[seq_axis] = ps
        arena = jnp.zeros(arena_shape, jnp.float32)
        # pre-seed the arena with the dense content so untouched rows match
        for b in range(B):
            for v in range(nb):
                sl = [slice(None)] * dense.ndim
                sl[seq_axis] = slice(v * ps, (v + 1) * ps)
                arena = arena.at[1 + b * nb + v].set(dense[tuple(sl)][b])
        arena = layers.update_cache_pages(arena, src, pos, bt,
                                          seq_axis=seq_axis)
        got = jnp.concatenate(
            [jnp.concatenate([arena[bt[b, v]] for v in range(nb)],
                             axis=seq_axis - 1)[None]
             for b in range(B)])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_pad_rows_land_on_scratch_page(self):
        """A zero block table routes every write to page 0 — the engine's
        pad/overhang contract: real pages stay untouched."""
        rng = np.random.default_rng(1)
        arena = jnp.asarray(rng.normal(size=(4, 2, 8, 4)), jnp.float32)
        src = jnp.ones((1, 2, 3, 4), jnp.float32)
        bt = jnp.zeros((1, 4), jnp.int32)
        out = layers.update_cache_pages(arena, src, jnp.asarray([5]), bt)
        np.testing.assert_array_equal(np.asarray(out[1:]),
                                      np.asarray(arena[1:]))
        assert not np.array_equal(np.asarray(out[0]), np.asarray(arena[0]))


class TestPagedAttentionKernels:
    B, Hq, Hkv, D, PS, NB = 3, 4, 2, 64, 8, 4
    S = PS * NB

    def _fixture(self, seed=0):
        rng = np.random.default_rng(seed)
        k = jnp.asarray(rng.normal(size=(self.B, self.Hkv, self.S, self.D)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(self.B, self.Hkv, self.S, self.D)),
                        jnp.float32)
        kp, bt = scatter_pages(rng, k, self.PS, 1 + 2 * self.B * self.NB)
        vp, _ = scatter_pages(rng, v, self.PS, 1 + 2 * self.B * self.NB,
                              bt=bt)
        return rng, k, v, kp, vp, bt

    def test_gather_kv_pages_roundtrip(self):
        _, k, _, kp, _, bt = self._fixture()
        np.testing.assert_array_equal(
            np.asarray(ref.gather_kv_pages(kp, bt)), np.asarray(k))

    def test_ref_paged_chunk_matches_dense(self):
        rng, k, v, kp, vp, bt = self._fixture()
        T = 5
        q = jnp.asarray(rng.normal(size=(self.B, self.Hq, T, self.D)),
                        jnp.float32)
        pos = jnp.asarray([0, 9, 22], jnp.int32)
        want = ref.chunk_attention(q, k, v, pos=pos)
        got = ref.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)

    def test_ref_paged_decode_matches_dense(self):
        rng, k, v, kp, vp, bt = self._fixture(1)
        q = jnp.asarray(rng.normal(size=(self.B, self.Hq, self.D)),
                        jnp.float32)
        kv_len = jnp.asarray([1, 13, 32], jnp.int32)
        want = ref.decode_attention(q, k, v, kv_len=kv_len)
        got = ref.decode_attention_paged(q, kp, vp, block_table=bt,
                                        kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)

    def test_blocked_paged_matches_oracle(self):
        rng, _, _, kp, vp, bt = self._fixture(2)
        T = 3
        q = jnp.asarray(rng.normal(size=(self.B, self.Hq, T, self.D)),
                        jnp.float32)
        pos = jnp.asarray([2, 0, 17], jnp.int32)
        want = ref.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos)
        got = ref.chunk_attention_paged_blocked(q, kp, vp, block_table=bt,
                                                pos=pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    @pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4), (2, 1)])
    def test_pallas_chunk_paged_interpret(self, hq, hkv):
        """Pallas paged chunk kernel (interpret mode) == ref oracle,
        across GQA group sizes including Hkv=1 (the MLA latent shape)."""
        rng = np.random.default_rng(3)
        k = jnp.asarray(rng.normal(size=(self.B, hkv, self.S, self.D)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(self.B, hkv, self.S, self.D)),
                        jnp.float32)
        kp, bt = scatter_pages(rng, k, self.PS, 1 + 2 * self.B * self.NB)
        vp, _ = scatter_pages(rng, v, self.PS, 1 + 2 * self.B * self.NB,
                              bt=bt)
        T = 4
        q = jnp.asarray(rng.normal(size=(self.B, hq, T, self.D)),
                        jnp.float32)
        pos = jnp.asarray([0, 11, 25], jnp.int32)
        want = ref.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos)
        got = ops.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos,
                                        impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_pallas_chunk_paged_row_blocks(self):
        """128 q heads of one kv head x 6 tokens = 768 query rows, walked
        in three 256-row blocks whose starts are not multiples of T."""
        rng = np.random.default_rng(4)
        kv = [jnp.asarray(rng.normal(size=(self.B, 1, self.S, self.D)),
                          jnp.float32) for _ in range(2)]
        kp, bt = scatter_pages(rng, kv[0], self.PS, 1 + 2 * self.B * self.NB)
        vp, _ = scatter_pages(rng, kv[1], self.PS, 1 + 2 * self.B * self.NB,
                              bt=bt)
        q = jnp.asarray(rng.normal(size=(self.B, 128, 6, self.D)),
                        jnp.float32)
        pos = jnp.asarray([0, 11, 25], jnp.int32)
        want = ref.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos)
        got = ops.chunk_attention_paged(q, kp, vp, block_table=bt, pos=pos,
                                        impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_pallas_decode_paged_interpret(self):
        rng, _, _, kp, vp, bt = self._fixture(4)
        q = jnp.asarray(rng.normal(size=(self.B, self.Hq, self.D)),
                        jnp.float32)
        kv_len = jnp.asarray([3, 32, 18], jnp.int32)
        want = ref.decode_attention_paged(q, kp, vp, block_table=bt,
                                          kv_len=kv_len)
        got = ops.decode_attention_paged(q, kp, vp, block_table=bt,
                                         kv_len=kv_len, impl="pallas",
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_scratch_page_garbage_cannot_leak(self):
        """Block-table entries past each row's frontier can point anywhere
        (the engine leaves them 0 = the scratch page, which decode-tick
        overhang writes trash): masked columns must carry exactly-zero
        softmax mass in every paged variant."""
        rng, _, _, kp, vp, bt = self._fixture(5)
        pos = jnp.asarray([1, 9, 17], jnp.int32)   # frontiers mid-arena
        T = 2
        q = jnp.asarray(rng.normal(size=(self.B, self.Hq, T, self.D)),
                        jnp.float32)
        # zero out every block-table entry strictly past the frontier and
        # dump garbage on the scratch page
        bt2 = np.asarray(bt).copy()
        for b in range(self.B):
            first_unused = (int(pos[b]) + T - 1) // self.PS + 1
            bt2[b, first_unused:] = 0
        kp2 = kp.at[0].set(1e4)
        vp2 = vp.at[0].set(-1e4)
        for fn, kw in (
                (ref.chunk_attention_paged, {}),
                (ref.chunk_attention_paged_blocked, {}),
                (ops.chunk_attention_paged,
                 {"impl": "pallas", "interpret": True})):
            want = fn(q, kp, vp, block_table=bt, pos=pos, **kw)
            got = fn(q, kp2, vp2, block_table=jnp.asarray(bt2), pos=pos,
                     **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-5)


class TestArenaWriteIsolation:
    PAGES, PS = 12, 4

    @pytest.mark.parametrize("arch", PAGED_ARCHS)
    def test_layers_write_only_granted_pages(self, arch):
        """The arena of every layer rides whole through the layer scan:
        after a chunk call and a decode call, each layer has changed only
        the pages the block table granted and its own scratch page, and
        every other page of every layer is bit-identical."""
        cfg, model, params = build(arch)
        zeros = model.init_paged_cache(self.PAGES, self.PS)
        keys = jax.random.split(jax.random.key(3), len(zeros))
        before = {name: jax.random.normal(k, a.shape, a.dtype)
                  for k, (name, a) in zip(keys, sorted(zeros.items()))}
        # row 0 writes virtual rows 0..6 (pages 3, 5), row 1 rows 3..9
        # (pages 7, 2, 9); entries past the frontier stay on scratch
        bt = jnp.asarray([[3, 5, 0, 0], [7, 2, 9, 0]], jnp.int32)
        tokens = jnp.asarray(mixed_prompts(cfg, lengths=(6, 6)))
        _, arena, table = model.forward_chunk_paged(
            params, tokens, model.table(), before,
            jnp.asarray([0, 3], jnp.int32),
            bt, jnp.asarray([6, 6], jnp.int32))
        _, arena, _ = model.decode_step_paged(
            params, tokens[:, 0], table, arena,
            jnp.asarray([6, 9], jnp.int32), bt)
        granted = {2, 3, 5, 7, 9}
        for name in before:
            old, new = np.asarray(before[name]), np.asarray(arena[name])
            assert new.shape[:2] == (cfg.n_layers, self.PAGES)
            for layer in range(cfg.n_layers):
                changed = {pg for pg in range(self.PAGES)
                           if not np.array_equal(old[layer, pg],
                                                 new[layer, pg])}
                assert changed - {0} == granted, (name, layer, changed)


class TestPageAllocator:
    def test_reserve_grant_release_accounting(self):
        a = PageAllocator(9, 4)          # 8 usable (page 0 scratch)
        assert a.usable == 8
        assert a.pages_needed(1) == 1 and a.pages_needed(9) == 3
        assert a.try_reserve(1, 5)
        assert not a.try_reserve(2, 4)   # 5 committed, 4 > 3 left
        assert a.try_reserve(2, 3)
        got = a.grant(1, 2)
        assert len(got) == 2 and 0 not in got
        assert a.in_use == 2
        with pytest.raises(RuntimeError):
            a.grant(1, 4)                # exceeds uid 1's reservation (3)
        assert a.release(1) == 2
        a.cancel(2)
        assert a.in_use == 0 and a.hwm == 2
        assert a.try_reserve(3, 8)       # whole pool free again

    def test_rejects_degenerate_pools(self):
        with pytest.raises(ValueError):
            PageAllocator(1, 4)          # scratch page only
        with pytest.raises(ValueError):
            PageAllocator(4, 0)


def paged_scfg(chunk, *, max_batch=3, pages=40, page_size=8, **kw):
    return ServeConfig(max_batch=max_batch, max_seq_len=64, eos_token=-1,
                       prefill_chunk=chunk, min_chunk_bucket=4,
                       page_size=page_size, max_cache_pages=pages, **kw)


class TestPagedEngineEquivalence:
    # chunk=64: whole-prompt admission chunks, both pageable families;
    # chunk=3 (min_chunk_bucket=4): bucket-PADDED continuation chunks
    # whose pad/overhang rows write through zero block-table entries
    # onto the scratch page; chunk=1: token-at-a-time prefill crossing
    # page boundaries on every 8th tick
    @pytest.mark.parametrize("arch,chunk", [
        *[(a, 64) for a in PAGED_ARCHS],
        ("tinyllama_1_1b", 1), ("tinyllama_1_1b", 3),
        ("deepseek_v2_lite_16b", 3), (MULTI_STACK_MOE, 3),
    ])
    def test_paged_matches_contiguous_and_sequential(self, arch, chunk):
        cfg, model, params = build_paged(arch)
        prompts = mixed_prompts(cfg)
        max_new = [6, 5, 6, 4]

        def drive(paged):
            scfg = paged_scfg(chunk) if paged else ServeConfig(
                max_batch=3, max_seq_len=64, eos_token=-1,
                prefill_chunk=chunk, min_chunk_bucket=4)
            eng = ServingEngine(model, params, scfg)
            assert eng.paged == paged
            reqs = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
            eng.run_until_drained()
            return [r.output for r in reqs]

        paged_out = drive(True)
        assert paged_out == drive(False), f"{arch}: paged != contiguous"
        for out, p, n in zip(paged_out, prompts, max_new):
            assert out == sequential_decode(model, params, p, n), \
                f"{arch}: paged != sequential for prompt len {len(p)}"

    @pytest.mark.parametrize("arch", DENSE_ARCHS)
    def test_recurrent_families_fall_back_dense(self, arch):
        """Recurrent state is O(1) per slot — nothing to page.  Asking for
        pages anyway must degrade gracefully to the contiguous pool and
        stay sequential-identical."""
        cfg, model, params = build(arch)
        assert model.forward_chunk_paged is None
        eng = ServingEngine(model, params, paged_scfg(64))
        assert not eng.paged and eng.allocator is None
        prompts = mixed_prompts(cfg, lengths=(5, 9))
        reqs = [eng.submit(p, 5) for p in prompts]
        eng.run_until_drained()
        for r, p in zip(reqs, prompts):
            assert r.output == sequential_decode(model, params, p, 5)


class TestPageBackPressure:
    def test_exhaustion_backpressures_fcfs_without_reorder(self):
        """3 free slots but pages for ~one long request: the queue head
        waits on pages (not slots), younger requests may NOT jump it,
        and everyone eventually completes token-identically."""
        cfg, model, params = build("tinyllama_1_1b")
        rng = np.random.default_rng(11)
        long = rng.integers(0, cfg.vocab, 40).astype(np.int32)
        shorts = [rng.integers(0, cfg.vocab, 6).astype(np.int32)
                  for _ in range(2)]
        # 40+6-1 rows -> 6 pages of 8; 7 usable pages fit one long OR
        # both shorts (2 pages each), never a long plus anything
        eng = ServingEngine(model, params, paged_scfg(64, pages=8))
        r_long = eng.submit(long, max_new_tokens=6)
        r_shorts = [eng.submit(s, max_new_tokens=6) for s in shorts]
        eng.step()
        assert len(eng.scheduler.active()) == 1   # long admitted alone
        for _ in range(8):
            eng.step()
            # strict FCFS under page pressure: while the long request
            # holds the pool, the shorts stay queued even though slots
            # (and, for the second short, pages) are free
            if not r_long.done:
                assert len(eng.scheduler.active()) == 1
        eng.run_until_drained()
        assert r_long.done and all(r.done for r in r_shorts)
        assert r_long.output == sequential_decode(model, params, long, 6)
        for r, s in zip(r_shorts, shorts):
            assert r.output == sequential_decode(model, params, s, 6)
        assert eng.allocator.in_use == 0

    def test_impossible_request_fails_at_submit(self):
        cfg, model, params = build("tinyllama_1_1b")
        eng = ServingEngine(model, params, paged_scfg(64, pages=4))
        prompt = np.arange(40, dtype=np.int32) % cfg.vocab
        with pytest.raises(ValueError, match="pages"):
            eng.submit(prompt, max_new_tokens=8)
        # the pool is untouched and serviceable afterwards
        assert eng.allocator.in_use == 0
        r = eng.submit(prompt[:10], max_new_tokens=4)
        eng.run_until_drained()
        assert r.done


class TestPageRecycling:
    def test_two_waves_bounded_hwm_and_clean_drain(self):
        cfg, model, params = build("tinyllama_1_1b")
        eng = ServingEngine(model, params, paged_scfg(64, pages=24))
        prompts = mixed_prompts(cfg, seed=9, lengths=(9, 5, 12, 7))

        def wave():
            reqs = [eng.submit(p, 4) for p in prompts]
            eng.run_until_drained()
            assert all(r.done for r in reqs)

        wave()
        hwm1 = eng.allocator.hwm
        assert 0 < hwm1 <= eng.allocator.usable
        wave()
        assert eng.allocator.hwm == hwm1, \
            "second wave grew the page HWM: pages are not being recycled"
        assert eng.allocator.in_use == 0
        assert not eng.block_tables.any()
        # satellite: the bucket-pad gather scratch is per-TICK, not
        # retained for the engine's lifetime
        assert eng._pad_stashes == {}

    def test_pad_stashes_released_after_drain_dense_too(self):
        cfg, model, params = build("tinyllama_1_1b")
        eng = ServingEngine(model, params, ServeConfig(
            max_batch=3, max_seq_len=64, eos_token=-1, prefill_chunk=3,
            min_chunk_bucket=4))
        reqs = [eng.submit(p, 4) for p in mixed_prompts(cfg, seed=4)]
        eng.run_until_drained()
        assert all(r.done for r in reqs)
        assert eng._pad_stashes == {}


class TestPagedProgramBound:
    def test_chunk_program_lattice_survives_paging(self):
        """Paging threads one extra operand through forward_chunk; the
        (batch bucket, width) compiled-program set must not grow beyond
        the dense engine's on the same workload."""
        cfg, model, params = build("tinyllama_1_1b")
        prompts = mixed_prompts(cfg, seed=6, lengths=(3, 7, 5, 9, 11, 4))

        def programs(paged):
            scfg = paged_scfg(4) if paged else ServeConfig(
                max_batch=3, max_seq_len=64, eos_token=-1, prefill_chunk=4,
                min_chunk_bucket=4)
            eng = ServingEngine(model, params, scfg)
            for p in prompts:
                eng.submit(p, 3)
            eng.run_until_drained()
            return eng.chunk_programs

        assert programs(True) == programs(False)

    def test_paged_gauges_fold_into_profile_shard(self, tmp_path):
        cfg, model, params = build("tinyllama_1_1b")
        eng = ServingEngine(model, params, paged_scfg(
            64, profile_dir=str(tmp_path), profile_interval_ticks=1))
        for p in mixed_prompts(cfg, seed=8, lengths=(5, 9)):
            eng.submit(p, 4)
        eng.run_until_drained()
        eng.write_profile_shard()
        from repro.profile.store import ProfileStore
        edges = ProfileStore(str(tmp_path)).reduce().to_folded().edges
        apis = {k[2] for k in edges}
        for gauge in ("cache_pages_in_use", "cache_page_hwm",
                      "cache_pages_capacity"):
            assert gauge in apis, f"serve.{gauge} missing from shard"
