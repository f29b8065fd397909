"""Continuous-batching serving subsystem tests.

The load-bearing invariant: mixed-length prompts admitted at STAGGERED
ticks into the pooled engine must produce TOKEN-IDENTICAL outputs to
per-request sequential decode — which only holds if every slot advances
at its own position (per-slot `pos: [B]`: rope angles, row-range cache
scatters and offset-causal masks all per-row).  Prefill and decode are
ONE positioned-chunk operation (`forward_chunk`) at different widths, so
the equivalence is checked at chunk widths {1, 3, bucket, whole-prompt}
— including bucket-padded chunks whose pad is masked in-model — for
every model family the engine serves (dense, moe/mla, hybrid, ssm; vlm
and audio prompts need patches/frames at submit, which the token-prompt
client API doesn't carry; their chunk equivalence lives in
test_models.py).  Cross-slot BATCHED prefill (TestBatchedPrefill) adds
the second equivalence axis: same-tick chunks of different slots
running as one multi-row forward_chunk must be token-identical to the
per-slot path (prefill_batch=1) and to sequential decode, and must
never bend strict FCFS.  Plus the scheduler (admission + continuation
budget), the bounded compiled-program guarantee (now (batch bucket,
width) pairs), the pooled sampler
(determinism under batching), the client API (background thread,
streaming callbacks, futures), EOS-on-first-token, truncation
accounting, and the serve latency phases folded into profile shards.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serving import (SamplingParams, Scheduler, ServingEngine,
                           sample_tokens)

SERVING_ARCHS = ["tinyllama_1_1b", "deepseek_v2_lite_16b", "zamba2_2_7b",
                 "xlstm_1_3b"]


def tiny(arch):
    """Extra-reduced smoke config: 2 layers, small vocab, drop-free MoE."""
    return dataclasses.replace(get_smoke(arch), n_layers=2, vocab=256,
                               capacity_factor=8.0)


def build(arch, seed=0):
    cfg = tiny(arch)
    model = build_model(cfg, impl="ref")
    return cfg, model, model.init(jax.random.key(seed))


def sequential_decode(model, params, prompt, max_new, max_seq_len=64,
                      eos=-1):
    """Reference: full single-request prefill + one-at-a-time decode,
    greedy, with the engine's EOS/max_new semantics."""
    cache = model.init_cache(1, max_seq_len)
    table = model.table()
    lg, cache, table = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None])}, table, cache)
    toks = [int(jnp.argmax(lg[0]))]
    pos = len(prompt)
    while len(toks) < max_new and toks[-1] != eos:
        lg, cache, table = model.decode_step(
            params, jnp.asarray([toks[-1]], jnp.int32), table, cache,
            jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    return toks


def staggered_run(engine, prompts, max_new, sampling=None):
    """Submit mixed-length prompts at staggered ticks; drain; return reqs."""
    reqs = [engine.submit(prompts[0], max_new[0], sampling=sampling)]
    engine.step()
    engine.step()
    reqs.append(engine.submit(prompts[1], max_new[1], sampling=sampling))
    reqs.append(engine.submit(prompts[2], max_new[2], sampling=sampling))
    engine.step()
    reqs.append(engine.submit(prompts[3], max_new[3], sampling=sampling))
    engine.run_until_drained()
    return reqs


def mixed_prompts(cfg, seed=1, lengths=(3, 7, 5, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lengths]


def chunked_prefill_decode(model, params, prompt, max_new, width,
                           max_seq_len=64, pad_to=None):
    """Reference driver for forward_chunk: feed the prompt in `width`-token
    chunks at the running cache offset (optionally bucket-padding each
    chunk to `pad_to` with the pad masked via `valid`), then greedy-decode
    through width-1 chunks."""
    cache = model.init_cache(1, max_seq_len)
    table = model.table()
    pos = 0
    for start in range(0, len(prompt), width):
        seg = prompt[start:start + width]
        n = len(seg)
        w = max(pad_to or n, n)
        padded = np.zeros((w,), np.int32)
        padded[:n] = seg
        lg, cache, table = model.forward_chunk(
            params, jnp.asarray(padded[None]), table, cache,
            jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32))
        pos += n
    toks = [int(jnp.argmax(lg[0]))]
    while len(toks) < max_new:
        lg, cache, table = model.decode_step(
            params, jnp.asarray([toks[-1]], jnp.int32), table, cache,
            jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    return toks


class TestContinuousBatchingEquivalence:
    # chunk=64: every prompt fits one admission chunk (all families);
    # chunk=3: prompts prefill through bucket-padded 3-token continuation
    # chunks at mixed slot depths — engine-level, covered for one
    # KV-cache family and the hybrid (SSM state + shared attention KV);
    # the other families' chunk math is pinned by the model-level width-
    # equivalence test below (keeps tier-1 wall time in check)
    @pytest.mark.parametrize("arch,chunk", [
        *[(a, 64) for a in SERVING_ARCHS],
        ("tinyllama_1_1b", 3), ("zamba2_2_7b", 3),
    ])
    def test_staggered_matches_sequential(self, arch, chunk):
        """Pooled per-slot-position serving == per-request sequential
        decode, token for token, with requests arriving mid-flight."""
        cfg, model, params = build(arch)
        prompts = mixed_prompts(cfg)
        max_new = [6, 5, 6, 4]
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=3, max_seq_len=64, eos_token=-1, prefill_chunk=chunk,
            min_chunk_bucket=4))
        reqs = staggered_run(engine, prompts, max_new)
        for r, p, n in zip(reqs, prompts, max_new):
            assert r.done
            assert r.output == sequential_decode(model, params, p, n), \
                f"{arch}: batched != sequential for prompt len {len(p)}"

    @pytest.mark.parametrize("arch", SERVING_ARCHS)
    def test_forward_chunk_width_equivalence(self, arch):
        """forward_chunk is width-invariant: feeding a prompt at widths
        {1, 3, bucket-padded 4, whole-prompt} produces token-identical
        greedy continuations to the sequential prefill+decode path.  The
        width-3-padded-to-4 case exercises the in-model pad mask (valid)
        every bucketed engine chunk relies on."""
        cfg, model, params = build(arch)
        prompt = mixed_prompts(cfg, seed=5, lengths=(9,))[0]
        ref = sequential_decode(model, params, prompt, 5)
        for width, pad_to in ((1, None), (3, None), (3, 4), (len(prompt),
                                                            None)):
            got = chunked_prefill_decode(model, params, prompt, 5, width,
                                         pad_to=pad_to)
            assert got == ref, (f"{arch}: width {width} (pad {pad_to}) "
                                f"!= sequential: {got} vs {ref}")

    @pytest.mark.parametrize("arch", ["tinyllama_1_1b", "xlstm_1_3b"])
    def test_chunked_prefill_matches_single_slot(self, arch):
        """In-model chunked prefill (2-token continuation chunks) is
        batch-composition independent: a crowded pool reproduces the
        single-slot engine exactly, chunk boundaries and all."""
        cfg, model, params = build(arch)
        prompts = mixed_prompts(cfg, seed=2, lengths=(5, 9, 4, 7))
        max_new = [5, 4, 6, 5]
        mk = lambda batch: ServingEngine(model, params, ServeConfig(
            max_batch=batch, max_seq_len=64, eos_token=-1, prefill_chunk=2))
        crowded = staggered_run(mk(3), prompts, max_new)
        for r, p, n in zip(crowded, prompts, max_new):
            solo = mk(1)
            ref = solo.submit(p, n)
            solo.run_until_drained()
            assert r.output == ref.output, f"{arch}: chunked prefill " \
                f"depends on batch composition (prompt len {len(p)})"

    def test_tail_chunk_one_reproduces_token_feed(self):
        """tail_chunk=1 (the legacy one-token-per-tick comparison mode)
        still produces sequential-identical tokens through the unified
        chunk path."""
        cfg, model, params = build("tinyllama_1_1b")
        prompts = mixed_prompts(cfg, seed=7, lengths=(11, 6, 9, 8))
        max_new = [4, 5, 4, 5]
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=3, max_seq_len=64, eos_token=-1, prefill_chunk=4,
            tail_chunk=1, min_chunk_bucket=1))
        reqs = staggered_run(engine, prompts, max_new)
        for r, p, n in zip(reqs, prompts, max_new):
            assert r.output == sequential_decode(model, params, p, n)

    def test_sampled_decode_is_batch_independent(self):
        """Sampling keys derive from (seed, position): a request's sampled
        continuation is identical batched or solo."""
        cfg, model, params = build("tinyllama_1_1b")
        prompts = mixed_prompts(cfg, seed=3)
        max_new = [6, 6, 6, 6]
        sp = SamplingParams(temperature=0.8, top_k=12, top_p=0.9, seed=7)
        mk = lambda batch: ServingEngine(model, params, ServeConfig(
            max_batch=batch, max_seq_len=64, eos_token=-1, prefill_chunk=64))
        batched = staggered_run(mk(3), prompts, max_new, sampling=sp)
        for r, p, n in zip(batched, prompts, max_new):
            solo = mk(1)
            ref = solo.submit(p, n, sampling=sp)
            solo.run_until_drained()
            assert r.output == ref.output
            assert len(r.output) == n


class TestBatchedPrefill:
    """Cross-slot batched prefill: each tick's selected chunks group by
    compiled width and run as ONE multi-row forward_chunk (gathered
    stashes, per-row pos/valid, bucket-padded batch dim).  The invariant:
    batching changes HOW chunks execute, never WHAT tokens come out —
    batched runs must be token-identical to the per-slot path
    (prefill_batch=1) and to sequential per-request decode."""

    def mk(self, model, params, batch, **kw):
        base = dict(max_batch=4, max_seq_len=64, eos_token=-1,
                    prefill_chunk=8, min_chunk_bucket=4)
        base.update(kw)
        return ServingEngine(model, params,
                             ServeConfig(prefill_batch=batch, **base))

    @pytest.mark.parametrize("arch", SERVING_ARCHS)
    def test_concurrent_admissions_match_per_slot_and_sequential(self, arch):
        """Four same-tick admissions of mixed widths (two multi-chunk
        prompts): the batched engine groups them (one group bucket-padded
        B=3->4, plus continuation groups on later ticks) and must emit
        exactly the tokens the per-slot engine and sequential decode
        emit."""
        cfg, model, params = build(arch)
        prompts = mixed_prompts(cfg, seed=11, lengths=(3, 17, 5, 20))
        max_new = [5, 4, 5, 4]
        outs = {}
        for batch in (4, 1):
            engine = self.mk(model, params, batch)
            reqs = [engine.submit(p, n) for p, n in zip(prompts, max_new)]
            engine.run_until_drained()
            assert all(r.done for r in reqs)
            outs[batch] = [r.output for r in reqs]
            buckets = {b for b, _ in engine.chunk_programs}
            if batch > 1:   # batching actually engaged (multi-row groups)
                assert max(buckets) > 1, engine.chunk_programs
            else:           # prefill_batch=1 IS the per-slot path
                assert buckets == {1}, engine.chunk_programs
        assert outs[4] == outs[1], f"{arch}: batched != per-slot prefill"
        for out, p, n in zip(outs[4], prompts, max_new):
            assert out == sequential_decode(model, params, p, n), \
                f"{arch}: batched prefill != sequential (len {len(p)})"

    def test_staggered_mixed_width_ticks_match_per_slot(self):
        """Staggered arrivals where a tick mixes continuation chunks of
        older slots with fresh admissions at a DIFFERENT width: groups
        form per width, and outputs still match the per-slot path."""
        cfg, model, params = build("tinyllama_1_1b")
        prompts = mixed_prompts(cfg, seed=12, lengths=(19, 4, 18, 6))
        max_new = [4, 5, 4, 5]
        runs = {b: staggered_run(self.mk(model, params, b, tail_chunk=4),
                                 prompts, max_new) for b in (4, 1)}
        for rb, r1, p, n in zip(runs[4], runs[1], prompts, max_new):
            assert rb.output == r1.output
            assert rb.output == sequential_decode(model, params, p, n)

    def test_width_one_chunks_batch_across_slots(self):
        """Degenerate width-1 chunks (prefill_chunk=1, unit bucket) still
        batch across slots and stay sequential-identical — the finest
        grain the compiled-width lattice reaches."""
        cfg, model, params = build("tinyllama_1_1b")
        prompts = mixed_prompts(cfg, seed=13, lengths=(3, 5, 4))
        engine = self.mk(model, params, 4, prefill_chunk=1,
                         min_chunk_bucket=1)
        reqs = [engine.submit(p, 4) for p in prompts]
        engine.run_until_drained()
        assert any(b > 1 for b, _ in engine.chunk_programs), \
            engine.chunk_programs
        for r, p in zip(reqs, prompts):
            assert r.output == sequential_decode(model, params, p, 4)

    def test_bounded_chunk_programs(self):
        """The recompile hazard, now 2-D: many distinct prompt lengths
        under many admission patterns must stay on the O(log
        prefill_batch x log max_seq_len) lattice of (batch bucket, width)
        pairs — never one program per (group size, length)."""
        cfg, model, params = build("tinyllama_1_1b")
        rng = np.random.default_rng(14)
        engine = self.mk(model, params, 4, prefill_chunk=16,
                         min_chunk_bucket=8)
        lengths = list(range(3, 27, 2))          # 12 distinct prompt lengths
        for n in lengths:
            engine.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), 2)
        done = engine.run_until_drained()
        assert len(done) == len(lengths)
        assert engine.batch_buckets() == [1, 2, 4]
        lattice = {(b, w) for b in (1, 2, 4) for w in (8, 16)}
        assert engine.chunk_programs <= lattice, engine.chunk_programs
        assert engine.chunk_widths <= {8, 16}

    def test_occupancy_gauge_folds_into_profile(self, tmp_path):
        """Every batched call folds prefill_batch_occupancy (percent of
        compiled rows that were real slots) — the flow-graph evidence
        that batching engages; mean must land in (0, 100]."""
        cfg, model, params = build("tinyllama_1_1b")
        run_dir = str(tmp_path / "serve-run")
        engine = self.mk(model, params, 4, profile_dir=run_dir)
        for p in mixed_prompts(cfg, seed=15, lengths=(6, 6, 7)):
            engine.submit(p, 2)
        engine.run_until_drained()
        from repro.profile import ProfileStore
        folded = ProfileStore(run_dir).reduce().to_folded()
        occ = [e for k, e in folded.edges.items()
               if k[2] == "prefill_batch_occupancy"]
        assert occ and occ[0].count >= 1
        mean = occ[0].total_ns / occ[0].count
        assert 0 < mean <= 100, mean

    def test_older_continuation_blocks_younger_admission_batch(self):
        """Strict-FCFS regression under batched plans: while an older
        slot still owes continuation chunks and the per-tick budget is
        exhausted, a FULL batch of younger admissions must keep waiting
        — grouping happens after selection, so batching must never let
        younger admissions ride along in the older slot's group."""
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=4, max_seq_len=64, eos_token=-1, prefill_chunk=4,
            prefill_budget_tokens=4, min_chunk_bucket=4, prefill_batch=4))
        old = engine.submit(mixed_prompts(cfg, seed=9, lengths=(20,))[0], 2)
        engine.step()              # admits old, prefills its first chunk
        assert old.admitted_at is not None
        youngers = [engine.submit(p, 2)
                    for p in mixed_prompts(cfg, seed=10, lengths=(4, 4, 4))]
        while engine.scheduler.slots[0].pending:
            assert all(r.admitted_at is None for r in youngers), \
                "younger admissions rode along with an older continuation"
            engine.step()
        engine.run_until_drained()
        assert old.done and all(r.done for r in youngers)


class TestEngineSemantics:
    def test_first_token_eos_finishes_immediately(self):
        """A request whose FIRST sampled token is EOS must finish at admit
        time, not decode max_new_tokens - 1 further ticks."""
        cfg, model, params = build("tinyllama_1_1b")
        prompt = mixed_prompts(cfg)[0]
        first = sequential_decode(model, params, prompt, 1)[0]
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=first, prefill_chunk=64))
        req = engine.submit(prompt, max_new_tokens=16)
        ticks_before = engine._ticks
        engine.run_until_drained()
        assert req.done and req.output == [first]
        # the pool never decoded for it: one tick observes the empty pool
        assert engine._ticks - ticks_before <= 1

    def test_bounded_compiled_chunk_widths(self):
        """The per-admission recompile hazard: distinct prompt lengths
        must NOT each compile their own prefill program.  With
        power-of-two bucketing (pad masked in-model via `valid`), 12
        distinct lengths share O(log max_seq_len) compiled widths; with
        bucketing off, every distinct length is its own program."""
        cfg, model, params = build("tinyllama_1_1b")
        rng = np.random.default_rng(4)
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1, prefill_chunk=32,
            min_chunk_bucket=8))
        lengths = list(range(3, 27, 2))          # 12 distinct prompt lengths
        for n in lengths:
            engine.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), 2)
        done = engine.run_until_drained()
        assert len(done) == len(lengths)
        assert engine.chunk_widths <= {8, 16, 32}, engine.chunk_widths
        assert set(engine.chunk_buckets()) == {8, 16, 32}
        raw = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1, prefill_chunk=32,
            bucket_chunks=False))
        for n in lengths[:4]:
            raw.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), 2)
        raw.run_until_drained()
        assert len(raw.chunk_widths) == 4

    def test_widths_stay_pow2_on_non_pow2_rows(self):
        """End-of-row chunks must bucket DOWN (consuming fewer tokens),
        never compile an exact remainder width: a non-power-of-two
        max_seq_len row with near-full prompts stays on power-of-two
        compiled widths."""
        cfg, model, params = build("tinyllama_1_1b")
        rng = np.random.default_rng(6)
        # prefill_chunk 35 on a 50-row: the admission bucket (64) always
        # overshoots the row and must bucket down to 32
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=50, eos_token=-1, prefill_chunk=35,
            min_chunk_bucket=4))
        for n in (47, 45, 43):                   # near-full, distinct tails
            req = engine.submit(
                rng.integers(0, cfg.vocab, n).astype(np.int32), 2)
        engine.run_until_drained()
        assert req.done
        assert all(w & (w - 1) == 0 for w in engine.chunk_widths), \
            engine.chunk_widths
        assert len(engine.chunk_widths) <= 4, engine.chunk_widths

    def test_malformed_prompt_rejected_per_request(self):
        """An empty or non-1-D prompt must raise at submit() — failing
        later inside _admit would kill the engine loop and fail every
        other live client."""
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1))
        with pytest.raises(ValueError, match="non-empty 1-D"):
            engine.submit(np.array([], np.int32), 3)
        with pytest.raises(ValueError, match="non-empty 1-D"):
            engine.submit(np.zeros((2, 3), np.int32), 3)
        # the engine is still healthy for well-formed requests
        req = engine.submit(mixed_prompts(cfg)[0], max_new_tokens=2)
        engine.run_until_drained()
        assert req.done and len(req.output) == 2

    def test_truncated_prompt_flagged_and_counted(self):
        cfg, model, params = build("tinyllama_1_1b")
        from repro.core.tracer import TRACER
        from repro.profile import tracer_folded
        before = sum(
            e.count for k, e in tracer_folded().edges.items()
            if k[2] == "truncated_prompt")
        rng = np.random.default_rng(0)
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=1, max_seq_len=32, eos_token=-1))
        req = engine.submit(rng.integers(0, cfg.vocab, 40), max_new_tokens=4)
        engine.run_until_drained()
        assert req.done and req.truncated
        # prompt was cut to fit the cache row alongside max_new_tokens
        assert len(req.output) == 4
        after = sum(
            e.count for k, e in tracer_folded().edges.items()
            if k[2] == "truncated_prompt")
        assert after == before + 1

    def test_oversized_max_new_clamped_to_cache_row(self):
        """max_new_tokens >= max_seq_len must not let a slot's pos run off
        the end of its cache row (writes would silently clamp and corrupt
        the newest position); the engine caps the generation budget."""
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=1, max_seq_len=16, eos_token=-1))
        prompt = mixed_prompts(cfg)[0][:5]
        req = engine.submit(prompt, max_new_tokens=64)
        engine.run_until_drained()
        assert req.done and req.truncated
        # prompt clamped to 1 token (limit = max(1, 16 - 64 - 1)), then
        # generation capped to the row's remaining capacity
        assert len(req.output) == 15
        slot_positions = [s.pos for s in engine.scheduler.slots]
        assert max(slot_positions) <= 16

    def test_background_thread_streams_and_futures(self):
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1)).start()
        try:
            streamed = []
            lock = threading.Lock()

            def on_token(req, tok):
                with lock:
                    streamed.append(tok)

            prompts = mixed_prompts(cfg)
            h1 = engine.submit(prompts[0], 5, on_token=on_token)
            h2 = engine.submit(prompts[1], 4)
            assert h1.result(timeout=60).done
            assert h2.result(timeout=60).done
            assert streamed == h1.output
            assert h1.output == sequential_decode(model, params,
                                                  prompts[0], 5)
        finally:
            engine.stop()
        # a second start() resumes service on the same pool
        engine.start()
        try:
            h3 = engine.submit(mixed_prompts(cfg)[2], 3)
            assert h3.result(timeout=60).done and len(h3.output) == 3
        finally:
            engine.stop()

    @staticmethod
    def _break_decode(engine):
        """Inject a mid-loop failure (malformed prompts no longer reach
        the loop — submit rejects them — so the decode step is the
        injection point for loop-failure semantics)."""
        def boom(*a, **k):
            raise RuntimeError("injected decode failure")
        engine._decode = boom

    def test_engine_failure_does_not_strand_clients(self):
        """An error inside the serve loop must surface on result(), not
        silently kill the daemon thread while clients block forever."""
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1)).start()
        self._break_decode(engine)
        try:
            bad = engine.submit(mixed_prompts(cfg)[0], 4)
            with pytest.raises(RuntimeError):
                bad.result(timeout=60)
            assert bad.error is not None
            # the dead engine rejects instead of enqueueing into a void
            with pytest.raises(RuntimeError):
                engine.submit(np.zeros((3,), np.int32), 2)
        finally:
            engine.stop()

    def test_sync_mode_failure_wakes_waiters_too(self):
        """The closed-loop driver shares the background loop's guarantee:
        an engine error marks every live request failed before raising."""
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1))
        self._break_decode(engine)
        bad = engine.submit(mixed_prompts(cfg)[0], 4)
        with pytest.raises(Exception):
            engine.step()
        assert bad.error is not None and bad._done_event.is_set()
        with pytest.raises(RuntimeError):
            engine.submit(np.zeros((3,), np.int32), 2)

    def test_zero_max_new_tokens_rejected(self):
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=1, max_seq_len=64))
        with pytest.raises(ValueError):
            engine.submit(np.zeros((3,), np.int32), max_new_tokens=0)

    def test_serve_phases_fold_into_profile_shard(self, tmp_path):
        cfg, model, params = build("tinyllama_1_1b")
        run_dir = str(tmp_path / "serve-run")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1,
            profile_dir=run_dir))
        for p in mixed_prompts(cfg)[:3]:
            engine.submit(p, 4)
        done = engine.run_until_drained()
        assert len(done) == 3
        for r in done:
            assert r.queue_wait_s is not None and r.queue_wait_s >= 0
            assert r.ttft_s is not None and r.ttft_s > 0
            assert r.e2e_s is not None and r.e2e_s >= r.ttft_s
        from repro.profile import ProfileStore, RunRegistry
        folded = ProfileStore(run_dir).reduce().to_folded()
        apis = {k[2] for k in folded.edges}
        for phase in ("queue_wait", "ttft", "decode_token", "e2e",
                      "admit", "prefill_chunk", "decode_tick"):
            assert phase in apis, f"missing serve phase {phase}"
        per_req = {k[2]: e for k, e in folded.edges.items()
                   if k[1] == "serve"}
        assert per_req["ttft"].count >= 3
        assert per_req["e2e"].count >= 3
        assert per_req["decode_token"].count \
            >= sum(len(r.output) for r in done) - 3  # first tokens at admit
        # the run is discoverable the way fleets query serving replicas
        runs = RunRegistry(str(tmp_path)).query(kind="serve")
        assert len(runs) == 1 and runs[0].config == cfg.name


class TestWorkload:
    def test_run_workload_closed_and_stats(self):
        from repro.serving import latency_stats, run_workload
        cfg, model, params = build("tinyllama_1_1b")
        engine = ServingEngine(model, params, ServeConfig(
            max_batch=2, max_seq_len=64, eos_token=-1))
        import time
        t0 = time.monotonic()
        done = run_workload(engine, mixed_prompts(cfg)[:3], 4, mode="closed")
        s = latency_stats(done, time.monotonic() - t0)
        assert s["requests"] == 3 and s["tokens"] == 12
        assert s["throughput_tok_s"] > 0
        assert 0 <= s["queue_wait_mean_s"] <= s["ttft_mean_s"]
        assert s["decode_s_per_tok"] > 0 and s["truncated"] == 0
        with pytest.raises(ValueError):
            run_workload(engine, [], 4, mode="bogus")


class TestScheduler:
    def mk(self, **kw):
        scfg = ServeConfig(max_batch=4, max_seq_len=64, **kw)
        return Scheduler(scfg)

    class Req:
        def __init__(self, n):
            self.prompt = np.zeros((n,), np.int32)

    def test_budget_caps_admissions_per_tick(self):
        sched = self.mk(prefill_chunk=8, prefill_budget_tokens=8)
        for n in (6, 6, 6):
            sched.add(self.Req(n))
        first = sched.schedule()
        assert len(first) == 1           # 6 + 6 would blow the 8-token budget
        sched.bind(first[0][0], first[0][1], pos=6, pending=())
        assert len(sched.schedule()) == 1

    def test_budget_charges_truncated_length(self):
        """A prompt that will be truncated to fit its cache row must be
        billed for the tokens actually prefilled, not its raw length."""
        sched = self.mk(prefill_chunk=512, prefill_budget_tokens=60)
        class Req:
            def __init__(self, n, max_new):
                self.prompt = np.zeros((n,), np.int32)
                self.max_new_tokens = max_new
        # raw len 10_000, truncated to 64 - 16 - 1 = 47 tokens
        assert sched.admit_cost(Req(10_000, 16)) == 47
        sched.add(Req(10_000, 16))
        sched.add(Req(8, 4))
        picked = sched.schedule()
        assert len(picked) == 2          # 47 + 8 fits the 60-token budget

    def test_head_of_line_long_prompt_never_starves(self):
        sched = self.mk(prefill_chunk=64, prefill_budget_tokens=8)
        sched.add(self.Req(40))          # cost 40 > budget 8
        picked = sched.schedule()
        assert len(picked) == 1          # admitted anyway (first of the tick)

    def test_fcfs_into_free_slots(self):
        sched = self.mk(prefill_chunk=8)
        reqs = [self.Req(4) for _ in range(6)]
        for r in reqs:
            sched.add(r)
        picked = sched.schedule()
        assert [r for _, r in picked] == reqs[:4]   # pool size caps at 4
        assert sched.has_waiting()

    def test_continuation_chunks_share_the_budget(self):
        """Mid-prefill slots advance by tail_chunk-sized chunks under the
        SAME per-tick budget admissions draw from; admissions only see
        the leftover (continuations belong to older requests) and wait
        entirely when an older continuation was deferred."""
        sched = self.mk(prefill_chunk=8, prefill_budget_tokens=10)
        sched.bind(0, self.Req(20), pos=8, pending=range(12))
        sched.bind(1, self.Req(13), pos=8, pending=range(5))
        plan, deferred = sched.continuation_plan()
        assert plan == [(0, 8)]       # 8 + 5 would blow the 10-token budget
        assert deferred               # slot 1 got nothing: admissions wait
        sched.add(self.Req(6))
        assert sched.schedule(spent=8) == []        # leftover can't fit 6
        assert len(sched.schedule()) == 1           # fresh tick: admits

    def test_oversized_continuation_is_not_a_barrier(self):
        """A mid-prefill chunk too big for the leftover budget is skipped,
        not a wall: a smaller OLDER-than-waiting chunk behind it still
        runs this tick (and the skip is reported as deferred)."""
        sched = self.mk(prefill_chunk=8, prefill_budget_tokens=10)
        sched.bind(0, self.Req(20), pos=8, pending=range(8))
        sched.bind(1, self.Req(20), pos=8, pending=range(8))
        sched.bind(2, self.Req(13), pos=8, pending=range(2))
        plan, deferred = sched.continuation_plan()
        assert plan == [(0, 8), (2, 2)] and deferred

    def test_continuation_order_is_admission_fcfs(self):
        sched = self.mk(prefill_chunk=4)
        sched.bind(2, self.Req(9), pos=4, pending=range(5))    # older
        sched.bind(0, self.Req(9), pos=4, pending=range(5))    # newer
        plan, deferred = sched.continuation_plan()
        assert [i for i, _ in plan] == [2, 0] and not deferred

    def test_first_continuation_never_starves(self):
        sched = self.mk(prefill_chunk=16, prefill_budget_tokens=4)
        sched.bind(0, self.Req(40), pos=16, pending=range(24))
        plan, deferred = sched.continuation_plan()
        assert plan == [(0, 16)] and not deferred   # first always fits

    def test_tail_chunk_defaults_to_prefill_chunk(self):
        assert self.mk(prefill_chunk=8).tail_chunk == 8
        assert self.mk(prefill_chunk=8, tail_chunk=1).tail_chunk == 1


class TestPooledSampler:
    def test_greedy_and_degenerate_knobs_match_argmax(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
        am = np.asarray(jnp.argmax(logits, -1))
        B = 4
        vec = lambda x, dt=np.float32: jnp.asarray(np.full((B,), x, dt))
        seed = jnp.zeros((B,), jnp.uint32)
        step = jnp.arange(B, dtype=jnp.int32)
        greedy = sample_tokens(logits, vec(0.0), vec(0, np.int32),
                               vec(1.0), seed, step)
        topk1 = sample_tokens(logits, vec(1.3), vec(1, np.int32),
                              vec(1.0), seed, step)
        topp0 = sample_tokens(logits, vec(1.3), vec(0, np.int32),
                              vec(1e-9), seed, step)
        np.testing.assert_array_equal(np.asarray(greedy), am)
        np.testing.assert_array_equal(np.asarray(topk1), am)
        np.testing.assert_array_equal(np.asarray(topp0), am)

    def test_seed_and_step_determine_tokens(self):
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
        B = 3
        vec = lambda x, dt=np.float32: jnp.asarray(np.full((B,), x, dt))
        args = (logits, vec(0.9), vec(8, np.int32), vec(0.95))
        step = jnp.asarray([4, 4, 9], jnp.int32)
        a = sample_tokens(*args, jnp.asarray([1, 1, 1], jnp.uint32), step)
        b = sample_tokens(*args, jnp.asarray([1, 1, 1], jnp.uint32), step)
        c = sample_tokens(*args, jnp.asarray([1, 2, 1], jnp.uint32), step)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # row 0 and 1 share logits distribution shapes but differ by seed
        assert not np.array_equal(np.asarray(a), np.asarray(c)) \
            or np.asarray(a)[1] == np.asarray(c)[1]

    def test_top_k_restricts_support(self):
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
        top4 = set(np.asarray(jnp.argsort(logits[0])[-4:]))
        for s in range(24):
            tok = sample_tokens(logits, jnp.asarray([1.5], jnp.float32),
                                jnp.asarray([4], jnp.int32),
                                jnp.asarray([1.0], jnp.float32),
                                jnp.asarray([s], jnp.uint32),
                                jnp.asarray([0], jnp.int32))
            assert int(tok[0]) in top4
