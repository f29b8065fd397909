"""Serving engine: iteration-level continuous batching behind a client API.

Layering of this package:

    scheduler.py  admission + prefill planning — FCFS queue -> free slots
                  and continuation chunks under a per-tick prefill budget
    sampling.py   per-request sampling params as per-slot vectors, ONE
                  jitted pooled sampler (greedy/temperature/top-k/top-p)
    engine.py     the slot pool + compiled positioned-chunk forward, the
                  background serving thread, and the client handles

EVERY model step is one `forward_chunk` — a T-token chunk written at
per-slot cache offsets: admission bulk prefill, mid-prompt continuation
chunks and the pooled decode tick are the same operation at different
widths (the model layer's rope angles, row-range cache scatters and
offset-causal masks are all per-row).  Prefill is batched ACROSS slots:
each tick's selected chunks (continuations + admissions) group by
compiled width (scheduler.batched_prefill_plan) and every group runs as
ONE multi-row forward_chunk — the participating slots' batch=1 cache
stashes gather into a [B]-row cache, advance at per-row `pos` with
per-row `valid`, and scatter back (rows whose prompt completes scatter
into the pool and sample their first token from that chunk's last-valid
logits).  Concurrent admissions therefore share the accelerator instead
of serializing batch=1 calls; `prefill_batch=1` reproduces the per-slot
path through the same code.  Decode then runs ONE compiled width-1
chunk over the whole pool at per-slot positions: true iteration-level
batching with zero recompilation as requests come and go.  Chunk widths
AND group batch dims round up to power-of-two buckets (pad masked
in-model via `valid`), so the set of compiled prefill programs is
O(log prefill_batch x log max_seq_len), not one per distinct prompt
length or admission pattern.

Paged KV-cache pool (ServeConfig.max_cache_pages > 0, transformer/MLA
families): the contiguous [max_batch, max_seq_len] cache becomes a fixed
arena of pages plus per-slot block tables (paging.PageAllocator owns the
accounting).  Admission is gated by FREE PAGES — the scheduler's page
gate reserves a request's worst-case pages (prompt + max_new - 1 rows)
or back-pressures the FCFS queue — and pages are granted lazily as a
slot's `pos` crosses page boundaries, recycled at finish.  Prefill
groups and the decode tick write straight into the shared arena through
the tables (no batch=1 stashes, no scatter); pages-in-use /
high-water-mark / capacity fold as `serve.cache_pages_*` gauges, the
saturation resource the cache-pressure detector reads.  Recurrent
families (mamba/xlstm/encdec), whose state is O(1) in sequence length,
keep the dense layout behind the same API.

Client API: `submit()` returns a Request handle immediately; tokens
stream through an optional `on_token` callback and `handle.result()`
blocks until completion.  `start()` runs the engine on a background
thread (open-loop serving); without it, `run_until_drained()` drives the
same loop synchronously (closed-loop benchmarks, tests).

XFA instrumentation ('serve'): admit (slot binding) and decode_tick are
traced boundaries, every batched chunk step folds a `prefill_chunk`
duration, and every batched call folds a `prefill_batch_occupancy`
gauge (percent of compiled rows that were real slots, not bucket pad) —
the flow graph separates prefill cost from decode cost per tick and
shows whether cross-slot batching engages.  Each tick is split into
scope edges in the order it runs: `plan` (continuation plan, schedule,
admissions), per prefill group `prefill_inputs` (host inputs, page
grants, table slice), `prefill_sync` (Wait: the group's
block_until_ready) and per completed row `first_token` (its logits
copy and sample_one), then under decode_tick `decode_inputs` (rebuild,
grants, uploads), `sample` (Wait: pooled sampler and its host read)
and `emit` (token callbacks, finishes).  `decode_stall` folds, per
tick that starts with decoding rows, the time from step() entry to the
decode dispatch once per such row (the inter-token gap's share spent
before the decode call); `prefill_residence` folds each request's
admission to first token; the `decode_pages` / `decode_page_slots`
gauges are the pages the decode call's rows hold and the block-table
slots the paged kernel's grid addresses;
queue_wait (Wait kind), ttft, decode_token and e2e latency phases fold
via tracer.record_duration (which also folds the bounded latency
histograms behind the p50/p95/p99 read-out); truncated_prompt is a count
event.  Requests carrying a deadline (submit(deadline_ms=...) or
ServeConfig.deadline_ms) additionally fold one deadline_met or
deadline_miss count event at finish — the signal the slo-violation
detector reads.  Shards land in the profile store exactly like trainer
shards — `repro.profile query --kind serve`, report/diff/timeline all
apply to serving runs natively.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.core import tracer as xfa
from repro.core.shadow import KIND_WAIT
from repro.models.api import Model

from .sampling import GREEDY, PooledSampler, SamplingParams
from .scheduler import Scheduler


@dataclasses.dataclass
class Request:
    """Client handle for one generation request.

    Returned by ServingEngine.submit; safe to read from other threads.
    `result()` blocks until the request finishes; `on_token` (if given)
    is invoked from the engine thread for every generated token."""
    uid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int = 32
    sampling: SamplingParams = GREEDY
    submitted_at: float = 0.0
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False            # prompt cut to fit the cache row
    #: e2e latency contract in ms (None: untracked); at finish the engine
    #: folds deadline_met/deadline_miss and sets `deadline_missed`
    deadline_ms: Optional[float] = None
    deadline_missed: Optional[bool] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    on_token: Optional[Callable[["Request", int], None]] = None
    error: Optional[BaseException] = None      # engine failure, if any
    _done_event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)

    def result(self, timeout: Optional[float] = None) -> "Request":
        """Block until the request completes; raises TimeoutError, or
        RuntimeError if the engine failed while this request was live."""
        if not self._done_event.wait(timeout):
            raise TimeoutError(f"request {self.uid} not done in {timeout}s")
        if self.error is not None:
            raise RuntimeError(
                f"serving engine failed while request {self.uid} was "
                f"in flight") from self.error
        return self

    # -- latency accessors (None until the phase happened) ------------------
    @property
    def queue_wait_s(self) -> Optional[float]:
        return None if self.admitted_at is None \
            else self.admitted_at - self.submitted_at

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.first_token_at is None \
            else self.first_token_at - self.submitted_at

    @property
    def e2e_s(self) -> Optional[float]:
        return None if self.finished_at is None \
            else self.finished_at - self.submitted_at


def _scatter_slot(pool, one, slot_idx: int):
    """Write a batch=1 cache pytree into row `slot_idx` of the pool cache.

    The batch axis differs per family/leaf ([L,B,...] KV rows, xlstm's
    [n_super,n_m,B,...] states, ...) — it is inferred per leaf as the
    first axis where the batch=1 tree has extent 1 and the pool differs.
    (The previous engine hardcoded axis 1, which silently aliased every
    xlstm request onto batch row 0.)"""
    def leaf(p, o):
        if p.shape == o.shape:         # max_batch == 1: full replace
            return o.astype(p.dtype)
        ax = next(d for d, (a, b) in enumerate(zip(p.shape, o.shape))
                  if b == 1 and a != b)
        idx = [0] * p.ndim
        idx[ax] = slot_idx
        return jax.lax.dynamic_update_slice(p, o.astype(p.dtype), tuple(idx))
    return jax.tree.map(leaf, pool, one)


class ServingEngine:
    def __init__(self, model: Model, params, scfg: ServeConfig) -> None:
        self.model = model
        self.params = params
        self.scfg = scfg
        if scfg.xfa_overhead_budget > 0:
            # adaptive overhead governor: per-tick boundaries back off to
            # 1-in-k timing under load, counting stays exact (core.sampler)
            xfa.TRACER.set_overhead_budget(scfg.xfa_overhead_budget)
        self.scheduler = Scheduler(scfg)
        self.sampler = PooledSampler(scfg.max_batch)
        self.table = model.table()
        # paged pool: swap the contiguous [max_batch, max_seq_len] cache
        # for a page arena + per-slot block tables, admission gated by
        # free pages.  Families without a paged entry point (recurrent
        # state is O(1) in sequence length) keep the dense layout even
        # when max_cache_pages is set — same engine API either way.
        self.paged = bool(scfg.max_cache_pages > 0
                          and model.forward_chunk_paged is not None)
        self.allocator = None
        if self.paged:
            from .paging import PageAllocator
            self.allocator = PageAllocator(scfg.max_cache_pages,
                                           scfg.page_size)
            # virtual pages per slot: covers a full max_seq_len row (the
            # block table is the slot's whole address space; unassigned
            # entries point at scratch page 0)
            self._n_blocks = -(-scfg.max_seq_len // scfg.page_size)
            self.block_tables = np.zeros(
                (scfg.max_batch, self._n_blocks), np.int32)
            self.cache = model.init_paged_cache(scfg.max_cache_pages,
                                                scfg.page_size)
            self._decode = jax.jit(model.decode_step_paged,
                                   donate_argnums=(3,))
            self._chunk = jax.jit(model.forward_chunk_paged,
                                  donate_argnums=(3,))
            self.scheduler.page_gate = self._page_gate
        else:
            self.cache = model.init_cache(scfg.max_batch, scfg.max_seq_len)
            self._decode = jax.jit(model.decode_step, donate_argnums=(3,))
            self._chunk = jax.jit(model.forward_chunk, donate_argnums=(3,))
        # one compiled program per (BATCH BUCKET, CHUNK WIDTH) pair (both
        # bucketed powers of two); _chunk_programs tracks the scheduled
        # set — tests assert it stays bounded regardless of how many
        # distinct prompt lengths or admission patterns arrive
        self._chunk_programs: set = set()
        # per-leaf batch axes of the cache pytree (-1: unbatched leaf),
        # inferred once from shapes — the batch axis differs per
        # family/leaf ([L,B,...] KV rows, xlstm's [n_super,n_m,B,...]
        # states, ...) and the batched-prefill gather/scatter needs it
        s1 = jax.eval_shape(lambda: model.init_cache(1, scfg.max_seq_len))
        s2 = jax.eval_shape(lambda: model.init_cache(2, scfg.max_seq_len))
        self._batch_axes = jax.tree.map(
            lambda a, b: next(
                (d for d, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y), -1), s1, s2)
        self._pad_stashes: dict = {}
        self._uid = 0
        self.completed: List[Request] = []
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._error: Optional[BaseException] = None   # terminal loop failure
        self._profile_store = None
        self._publisher = None
        self._ticks = 0
        if scfg.profile_dir:
            from repro.profile import (ProfileStore, RetentionPolicy,
                                       register_run)
            self._profile_store = ProfileStore(
                scfg.profile_dir,
                retention=RetentionPolicy(
                    keep_last=scfg.profile_keep_last,
                    max_age_s=scfg.profile_max_age_s,
                    max_bytes=scfg.profile_max_bytes))
            # index this replica in the run registry so fleets of serving
            # runs are queryable (`repro.profile query --kind serve ...`)
            from repro.parallel.axes import get_runtime_mesh
            mesh = get_runtime_mesh()
            register_run(
                scfg.profile_dir,
                config=model.cfg.name, arch=model.cfg.family,
                mesh_shape=tuple(mesh.devices.shape)
                if mesh is not None else None,
                mesh_axes=tuple(mesh.axis_names)
                if mesh is not None else None,
                label=scfg.profile_label, kind="serve",
                meta={"max_batch": scfg.max_batch,
                      "max_seq_len": scfg.max_seq_len,
                      **({"page_size": scfg.page_size,
                          "max_cache_pages": scfg.max_cache_pages}
                         if self.paged else {}),
                      **dict(scfg.profile_meta)})
            if scfg.xfa_collector:
                from repro.profile import FleetPublisher
                self._publisher = FleetPublisher(scfg.xfa_collector,
                                                 scfg.profile_dir)

    # -- client API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               sampling: Optional[SamplingParams] = None,
               on_token: Optional[Callable[[Request, int], None]] = None,
               deadline_ms: Optional[float] = None) -> Request:
        """Enqueue a request; returns its handle immediately.

        `deadline_ms` sets this request's e2e latency contract (falls
        back to ServeConfig.deadline_ms when that is > 0): at finish the
        engine folds a deadline_met/deadline_miss count event and flags
        the handle, feeding the slo-violation detector.  The deadline is
        observational — a late request still completes."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the engine "
                             "always samples at least the first token)")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            # reject per-request: a malformed prompt failing inside
            # _admit would kill the engine loop and every other client
            raise ValueError(f"prompt must be a non-empty 1-D token "
                             f"array, got shape {prompt.shape}")
        if sampling is None:
            sampling = SamplingParams(
                temperature=self.scfg.temperature, top_k=self.scfg.top_k,
                top_p=self.scfg.top_p, seed=self.scfg.sample_seed)
        if deadline_ms is None and self.scfg.deadline_ms > 0:
            deadline_ms = self.scfg.deadline_ms
        # fit the request to the cache row AT SUBMIT, not mid-prefill:
        # the client sees the truncation on the handle it got back, and
        # the paged admission gate prices the rows that will really be
        # used.  Keep at least one prompt token even when max_new_tokens
        # alone (nearly) fills the row — matches Scheduler.admit_cost.
        truncated = False
        limit = max(1, self.scfg.max_seq_len - max_new_tokens - 1)
        if prompt.size > limit:
            # visible truncation: flagged on the handle AND folded as a
            # count event so fleets can alarm on it
            prompt = prompt[:limit]
            truncated = True
            xfa.count_event("serve", "truncated_prompt")
        cap = self.scfg.max_seq_len - prompt.size
        if max_new_tokens > cap:
            # generation budget clamped so the slot's pos can never run
            # off the end of its cache row (oversized max_new_tokens)
            max_new_tokens = cap
            truncated = True
            xfa.count_event("serve", "clamped_max_new")
        if self.paged:
            # a request whose worst case exceeds the whole pool could
            # never pass the page gate: structured rejection here instead
            # of a silent deadlock at the head of the FCFS queue
            rows = int(prompt.size) + max_new_tokens - 1
            need = self.allocator.pages_needed(rows)
            if need > self.allocator.usable:
                raise ValueError(
                    f"request needs {need} cache pages ({rows} rows at "
                    f"page_size={self.scfg.page_size}) but the pool has "
                    f"only {self.allocator.usable} usable pages "
                    f"(max_cache_pages={self.scfg.max_cache_pages}, "
                    f"page 0 reserved)")
        # timestamp BEFORE taking the lock: a tick in progress holds it,
        # and that wait is queueing delay the client really experienced
        submitted_at = time.monotonic()
        with self._work:
            if self._error is not None:
                # a dead engine must reject, not enqueue into a void where
                # result() would block forever
                raise RuntimeError("serving engine has failed; no further "
                                   "requests accepted") from self._error
            self._uid += 1
            req = Request(self._uid, prompt,
                          max_new_tokens, sampling=sampling,
                          submitted_at=submitted_at, on_token=on_token,
                          deadline_ms=deadline_ms, truncated=truncated)
            self.scheduler.add(req)
            self._work.notify_all()
        return req

    def start(self) -> "ServingEngine":
        """Run the engine loop on a background daemon thread.  After a
        timed-out stop() this blocks until the old loop finishes its tick
        and is reaped — there is never a second loop over the same pool,
        and start() returning means the engine IS serving."""
        while True:
            with self._lock:
                if self._error is not None:
                    raise RuntimeError("serving engine has failed; it "
                                       "cannot be restarted") from self._error
                t = self._thread
                if t is None:
                    self._stop = False
                    self._thread = threading.Thread(
                        target=self._serve_loop, name="serve-engine",
                        daemon=True)
                    self._thread.start()
                    return self
                if t.is_alive() and not self._stop:
                    return self            # genuinely running
            # finished, or stopping after a timed-out stop(): reap OUTSIDE
            # the lock (the loop's current tick needs it to complete)
            t.join()
            with self._lock:
                if self._thread is t:
                    self._thread = None

    def stop(self, timeout: float = 30.0) -> bool:
        """Stop the background thread (in-flight requests stay in place).
        Returns False if the loop is still finishing its current tick —
        the thread stays owned so a later start() can never spawn a
        second loop over the same pool; call stop() again to reap it."""
        with self._work:
            if self._thread is None:
                return True
            self._stop = True
            self._work.notify_all()
            t = self._thread
        t.join(timeout)
        if t.is_alive():
            return False
        with self._lock:
            if self._thread is t:
                self._thread = None
        if self._publisher is not None:
            self._publisher.close()
        return True

    # -- engine internals ---------------------------------------------------
    def chunk_buckets(self) -> list:
        """Every chunk width this engine schedules under bucketing — the
        warmup surface for benchmarks (compile these outside any timed
        window).  End-of-row chunks may additionally bucket DOWN to
        smaller powers of two; all widths stay powers of two, so the
        compiled-program count is O(log) regardless of prompt lengths."""
        scfg = self.scfg
        if not scfg.bucket_chunks:
            return []                  # unbounded: one program per length
        out, w = [], max(scfg.min_chunk_bucket, 1)
        top = max(scfg.prefill_chunk or 1, scfg.tail_chunk or 1)
        while w < top:
            out.append(w)
            w *= 2
        out.append(w)
        return out

    def batch_buckets(self) -> list:
        """Every compiled batch dimension batched prefill can schedule
        (powers of two up to the effective prefill_batch cap) — with
        chunk_buckets(), the warmup surface for benchmarks (one compiled
        program per (batch bucket, width) pair)."""
        if not self.scfg.bucket_chunks:
            return []                  # unbounded: one program per group size
        out, b = [], 1
        while b < self.scheduler.prefill_batch:
            out.append(b)
            b *= 2
        out.append(b)
        return out

    def warm_chunk_programs(self) -> None:
        """Compile every (batch bucket, width) prefill program this
        engine can schedule, on scratch caches — call it outside any
        timed window so a benchmark's first batched tick measures the
        batching, not XLA compilation.  Warmed programs do NOT count
        toward chunk_programs: that set reports what the workload
        actually scheduled (the recompile-hazard bound)."""
        for w in self.chunk_buckets() or [self.scfg.prefill_chunk or 1]:
            for b in self.batch_buckets() or [1]:
                if self.paged:
                    # the arena shape is part of the compiled program, so
                    # warm against a scratch arena of the SAME size; an
                    # all-zero block table routes every write to the
                    # scratch page
                    cache = self.model.init_paged_cache(
                        self.scfg.max_cache_pages, self.scfg.page_size)
                    logits, _, self.table = self._chunk(
                        self.params, jnp.zeros((b, w), jnp.int32),
                        self.table, cache, jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b, self._n_blocks), jnp.int32),
                        jnp.ones((b,), jnp.int32))
                else:
                    cache = self.model.init_cache(b, self.scfg.max_seq_len)
                    logits, _, self.table = self._chunk(
                        self.params, jnp.zeros((b, w), jnp.int32),
                        self.table, cache, jnp.zeros((b,), jnp.int32),
                        jnp.ones((b,), jnp.int32))
                jax.block_until_ready(logits)

    @property
    def chunk_widths(self) -> frozenset:
        """Chunk widths compiled so far (the width projection of
        chunk_programs; stays bounded no matter how many distinct prompt
        lengths arrive)."""
        return frozenset(w for _, w in self._chunk_programs)

    @property
    def chunk_programs(self) -> frozenset:
        """(batch_bucket, width) pairs scheduled so far — tests assert
        this stays O(log prefill_batch x log max_seq_len) no matter how
        many distinct prompt lengths or admission patterns arrive."""
        return frozenset(self._chunk_programs)

    # -- batched cross-slot prefill -----------------------------------------
    def _pad_stash(self, rows: int):
        """Zero cache rows padding a group up to its batch bucket (valid
        masks them in-model).  Cached per size: the gather CONCATENATES
        it (a copy) and only the copy is donated to the compiled call,
        so the cached rows stay live across ticks."""
        if rows not in self._pad_stashes:
            self._pad_stashes[rows] = self.model.init_cache(
                rows, self.scfg.max_seq_len)
        return self._pad_stashes[rows]

    def _gather_stashes(self, stashes: list, pad: int):
        """Concatenate B batch=1 stashes (+ `pad` zero rows) into one
        [B+pad]-row cache along each leaf's batch axis — _scatter_slot's
        machinery in reverse.  A single stash with no pad passes through
        untouched: prefill_batch=1 IS the legacy per-slot path, same
        buffers, same numerics."""
        if len(stashes) == 1 and pad == 0:
            return stashes[0]
        parts = stashes + ([self._pad_stash(pad)] if pad else [])

        def leaf(ax, *ls):
            return ls[0] if ax < 0 else jnp.concatenate(ls, axis=ax)
        return jax.tree.map(leaf, self._batch_axes, *parts)

    def _take_row(self, gathered, row: int):
        """Slice row `row` of a gathered stash back out as a batch=1
        cache pytree (a copy, so the donated gathered buffer is never
        aliased by a live slot stash)."""
        def leaf(ax, l):
            return l if ax < 0 else jax.lax.slice_in_dim(
                l, row, row + 1, axis=ax)
        return jax.tree.map(leaf, self._batch_axes, gathered)

    # -- paged pool ---------------------------------------------------------
    def _page_gate(self, req: Request) -> bool:
        """Scheduler admission gate: reserve the request's WORST-CASE
        pages (truncated prompt + clamped max_new - 1 rows — submit
        already fitted both to the row) or report back-pressure.  A True
        return has committed pages: _admit's slot consumes them via
        lazy grants, rollback paths cancel them."""
        rows = len(req.prompt) + req.max_new_tokens - 1
        return self.allocator.try_reserve(
            req.uid, self.allocator.pages_needed(rows))

    def _grant_rows(self, slot_idx: int, rows: int) -> None:
        """Ensure slot `slot_idx` owns pages covering its first `rows`
        cache rows, drawing lazily from the allocator as the frontier
        crosses page boundaries (granted page ids append to the slot's
        block table; page 0 is never granted, so count_nonzero IS the
        pages-held count)."""
        have = int(np.count_nonzero(self.block_tables[slot_idx]))
        need = self.allocator.pages_needed(rows) - have
        if need > 0:
            uid = self.scheduler.slots[slot_idx].request.uid
            pages = self.allocator.grant(uid, need)
            self.block_tables[slot_idx, have:have + need] = pages

    def _release_pages(self, slot_idx: int, req: Request) -> None:
        """Recycle a finished/failed slot's pages and clear its table."""
        if self.paged:
            self.allocator.release(req.uid)
            self.block_tables[slot_idx, :] = 0

    def _prefill_group(self, idxs: list, ns: list, width: int) -> None:
        """One batched prefill chunk: advance the B slots in `idxs` by
        their next ns[r] tokens through a SINGLE forward_chunk at
        per-row cache offsets (width bucket-padded in T, group padded to
        the batch bucket in B, both masked via `valid`).  Rows whose
        prompt completes scatter into the pool and sample their FIRST
        token from this chunk's last-valid logits — the TTFT win over
        the old one-token-per-tick tail feed, now at multi-slot
        throughput."""
        slots = self.scheduler.slots
        B = len(idxs)
        Bb = self.scheduler.batch_bucket(B)
        with xfa.scope("serve", "prefill_inputs"):
            tokens = np.zeros((Bb, width), np.int32)
            pos = np.zeros((Bb,), np.int32)
            valid = np.zeros((Bb,), np.int32)
            for r, (i, n) in enumerate(zip(idxs, ns)):
                slot = slots[i]
                tokens[r, :n] = [slot.pending.popleft() for _ in range(n)]
                pos[r] = slot.pos
                valid[r] = n
            if self.paged:
                # grant the pages this chunk's frontier will cross, then
                # run the group straight against the shared arena — no
                # stashes, no scatter: the block table IS the slot's cache
                # row.  Pad rows carry an all-zero table (writes land on
                # scratch).
                for i, n in zip(idxs, ns):
                    self._grant_rows(i, slots[i].pos + n)
                bt = np.zeros((Bb, self._n_blocks), np.int32)
                bt[:B] = self.block_tables[idxs]
                cache, rows = self.cache, (pos, bt)
            else:
                cache = self._gather_stashes(
                    [slots[i].stash for i in idxs], Bb - B)
                rows = (pos,)
            t0 = time.perf_counter_ns()
            args = (jnp.asarray(tokens), self.table, cache,
                    *map(jnp.asarray, rows), jnp.asarray(valid))
        logits, cache, self.table = self._chunk(self.params, *args)
        if self.paged:
            self.cache = cache
        else:
            gathered = cache
        # sync before the end timestamp: jitted calls return unready
        # arrays, and mid-prompt chunks have no downstream host read to
        # block on — without this the fold times dispatch, not compute
        with xfa.scope("serve", "prefill_sync", kind=KIND_WAIT):
            jax.block_until_ready(logits)
        # its own flow-graph edge: diagnose separates prefill interference
        # from decode cost per tick (wait-dominance / hot-edge detectors)
        xfa.record_duration("serve", "prefill_chunk",
                            time.perf_counter_ns() - t0)
        # batching efficiency as a gauge (percent of compiled rows that
        # were real slots): the flow-graph evidence that cross-slot
        # batching engages — 100 when groups fill their bucket, lower
        # when pad rows dominate (mean over calls via the gauge fold)
        xfa.record_gauge("serve", "prefill_batch_occupancy",
                         100.0 * B / Bb)
        self._chunk_programs.add((Bb, width))
        for r, (i, n) in enumerate(zip(idxs, ns)):
            slot = slots[i]
            slot.pos += n
            if not self.paged:
                row = gathered if B == 1 and Bb == 1 \
                    else self._take_row(gathered, r)
                if slot.pending:
                    slot.stash = row
                    continue
                self.cache = _scatter_slot(self.cache, row, i)
                slot.stash = None
            elif slot.pending:
                continue               # arena already holds the chunk
            # the first token is EOS-checked — a first-token EOS finishes
            # without any decode ticks instead of burning max_new - 1
            with xfa.scope("serve", "first_token"):
                tok = self.sampler.sample_one(
                    np.asarray(logits[r]), slot.request.sampling,
                    step=slot.pos)
            self._emit(i, tok, time.monotonic())

    @xfa.api("serve", "admit")
    def _admit(self, slot_idx: int, req: Request) -> int:
        """Bind `req` to slot `slot_idx` (truncation accounting, fresh
        batch=1 stash, sampler row) and return its first prefill chunk's
        token count — the chunk itself runs in this tick's batched
        prefill groups, alongside other admissions and continuations of
        the same compiled width."""
        model, scfg = self.model, self.scfg
        now = time.monotonic()
        req.admitted_at = now
        xfa.record_duration("serve", "queue_wait",
                            (now - req.submitted_at) * 1e9, kind=KIND_WAIT)
        # safety-net truncation for requests bound without going through
        # submit() (which already fitted prompt and max_new to the row —
        # these branches are then no-ops, so the count events fire once)
        limit = max(1, scfg.max_seq_len - req.max_new_tokens - 1)
        prompt = req.prompt
        if len(prompt) > limit:
            # visible truncation: flagged on the handle AND folded as a
            # count event so fleets can alarm on it
            prompt = prompt[:limit]
            req.truncated = True
            xfa.count_event("serve", "truncated_prompt")
        cap = scfg.max_seq_len - len(prompt)
        if req.max_new_tokens > cap:
            # generation budget clamped so the slot's pos can never run
            # off the end of its cache row (oversized max_new_tokens)
            req.max_new_tokens = cap
            req.truncated = True
            xfa.count_event("serve", "clamped_max_new")
        # paged pool: the slot writes straight into the shared arena
        # through its block table — no batch=1 stash to fill or scatter
        self.scheduler.bind(slot_idx, req, pos=0, pending=prompt,
                            stash=None if self.paged
                            else model.init_cache(1, scfg.max_seq_len))
        self.sampler.bind(slot_idx, req.sampling)
        return self.scheduler.admit_cost(req)

    @xfa.api("serve", "decode_tick")
    def _tick(self, stall_from: Optional[tuple] = None) -> int:
        """One pooled width-1 forward_chunk at per-slot positions over the
        slots past prefill; returns #decoding.  `stall_from` is
        (perf_counter_ns at step start, rows decoding then): the
        `decode_stall` those rows saw before this call's dispatch."""
        slots = self.scheduler.slots
        active = self.scheduler.decoding()
        if not active:
            return 0
        with xfa.scope("serve", "decode_inputs"):
            tokens = np.zeros((self.scfg.max_batch,), np.int32)
            pos = self.scheduler.pos_vector()
            for i in active:
                tokens[i] = slots[i].request.output[-1]
            if self.paged:
                # the write frontier (row `pos`) may cross into a new page
                for i in active:
                    self._grant_rows(i, slots[i].pos + 1)
                # pages the call's rows hold, against the block-table
                # slots the paged kernel's grid addresses (max_batch x
                # pages per row): the grid's live share is the ratio
                xfa.record_gauge("serve", "decode_pages",
                                 np.count_nonzero(self.block_tables[active]))
                xfa.record_gauge("serve", "decode_page_slots",
                                 self.block_tables.size)
            t0 = time.perf_counter_ns()
            args = (jnp.asarray(tokens), self.table, self.cache,
                    jnp.asarray(pos))
            if self.paged:
                args += (jnp.asarray(self.block_tables),)
        if stall_from is not None:
            # step start to this dispatch, once per row that was waiting
            # for it (weighted by tokens, as the inter-token gap is).
            # Every prefill group of the tick synced before this point,
            # so dispatch stands for device-ready here.
            t_step, n = stall_from
            xfa.record_duration("serve", "decode_stall",
                                time.perf_counter_ns() - t_step, n=n)
        logits, self.cache, self.table = self._decode(self.params, *args)
        with xfa.scope("serve", "sample", kind=KIND_WAIT):
            nxt = self.sampler(logits, step=pos + 1)
        tick_ns = time.perf_counter_ns() - t0
        now = time.monotonic()
        with xfa.scope("serve", "emit"):
            for i in active:
                slots[i].pos += 1
                self._emit(i, int(nxt[i]), now)
        xfa.record_duration("serve", "decode_token",
                            tick_ns / len(active), n=len(active))
        return len(active)

    def _emit(self, slot_idx: int, tok: int, now: float) -> None:
        """Accept one generated token for the request in `slot_idx`."""
        req = self.scheduler.slots[slot_idx].request
        first = not req.output
        req.output.append(tok)
        if first:
            req.first_token_at = now
            xfa.record_duration("serve", "ttft",
                                (now - req.submitted_at) * 1e9)
            # admission to first token: the request's time in prefill,
            # its own chunks and the ticks it shared with others
            xfa.record_duration("serve", "prefill_residence",
                                (now - req.admitted_at) * 1e9)
        if req.on_token is not None:
            try:
                req.on_token(req, tok)
            except Exception:
                xfa.count_event("serve", "callback_error")
        if tok == self.scfg.eos_token or len(req.output) >= req.max_new_tokens:
            self._finish(slot_idx, now)

    def _finish(self, slot_idx: int, now: float) -> None:
        req = self.scheduler.slots[slot_idx].request
        req.done = True
        req.finished_at = now
        e2e_ns = (now - req.submitted_at) * 1e9
        xfa.record_duration("serve", "e2e", e2e_ns)
        if req.deadline_ms is not None:
            req.deadline_missed = e2e_ns > req.deadline_ms * 1e6
            xfa.count_event("serve", "deadline_miss" if req.deadline_missed
                            else "deadline_met")
        self.completed.append(req)
        self._release_pages(slot_idx, req)
        self.scheduler.release(slot_idx)
        self.sampler.release(slot_idx)
        req._done_event.set()

    def step(self) -> int:
        """One engine iteration: continuation prefill chunks for
        mid-prompt slots (oldest first), admissions under the leftover
        budget, then one pooled decode tick.  Returns the number of
        slots still active afterwards.

        Failure handling lives HERE, not in the background loop, so the
        synchronous (closed-loop) driver gets the same guarantee: an
        error marks the engine dead and wakes every waiter before the
        exception propagates to whoever drove the step."""
        t_step = time.perf_counter_ns()
        with self._lock:
            try:
                n_decoding = len(self.scheduler.decoding())
                # queue depth at tick start, folded as a gauge: its
                # per-interval mean across the snapshot ring is the
                # saturation signal `diagnose` reads (a growing mean says
                # admission is structurally behind the arrival rate)
                xfa.record_gauge("serve", "queue_depth",
                                 len(self.scheduler.waiting))
                if self.paged:
                    # pages are the admission resource: fold occupancy,
                    # high-water mark and capacity as gauges so cache
                    # pressure is a flow-graph edge (what the
                    # cache-pressure detector and the fleet plane read)
                    xfa.record_gauge("serve", "cache_pages_in_use",
                                     self.allocator.in_use)
                    xfa.record_gauge("serve", "cache_page_hwm",
                                     self.allocator.hwm)
                    xfa.record_gauge("serve", "cache_pages_capacity",
                                     self.allocator.usable)
                with xfa.scope("serve", "plan"):
                    cont, deferred = self.scheduler.continuation_plan()
                    # strict FCFS: if any mid-prefill slot (older than
                    # every waiting request) was deferred by the budget,
                    # nothing younger may spend the leftover this tick
                    picked = [] if deferred else self.scheduler.schedule(
                        spent=sum(n for _, n in cont))
                    items = list(cont)
                    for k, (idx, req) in enumerate(picked):
                        try:
                            items.append((idx, self._admit(idx, req)))
                        except Exception as e:
                            # every request in `picked` was already
                            # popped from the queue — none may vanish
                            # without waking waiters: the failing one
                            # errors out, later ones go back to the queue
                            # head (FCFS preserved) for _fail_outstanding
                            # to find
                            req.error = e
                            req._done_event.set()
                            self._release_pages(idx, req)
                            self.scheduler.release(idx)
                            for _, later in reversed(picked[k + 1:]):
                                if self.paged:
                                    # the page gate reserved for them;
                                    # back in the queue they must not hold
                                    # pages (they re-reserve at their next
                                    # gate pass)
                                    self.allocator.cancel(later.uid)
                                self.scheduler.waiting.appendleft(later)
                            raise
                # continuations AND admissions batch together: one
                # forward_chunk per same-width group of selected chunks
                for idxs, ns, width in \
                        self.scheduler.batched_prefill_plan(items):
                    self._prefill_group(idxs, ns, width)
                # pad stashes are per-TICK scratch: groups in this tick
                # shared them by size, but holding them across ticks pins
                # dead full-context rows for the engine's lifetime
                self._pad_stashes.clear()
                self._tick((t_step, n_decoding) if n_decoding else None)
                self._ticks += 1
                interval = self.scfg.profile_interval_ticks
                if self._profile_store is not None and interval \
                        and self._ticks % interval == 0:
                    self.write_profile_shard()
                return len(self.scheduler.active())
            except Exception as e:      # noqa: BLE001 — fail loud AND clean
                self._fail_outstanding(e)
                raise

    def _serve_loop(self) -> None:
        xfa.set_thread_group("serve")
        while True:
            with self._work:
                while not self._stop and not self.scheduler.has_work():
                    self._work.wait(0.05)
                if self._stop:
                    break
            try:
                self.step()
            except Exception:               # noqa: BLE001 — must not die mute
                break                       # step() already failed waiters
        self.write_profile_shard()

    def _fail_outstanding(self, exc: BaseException) -> None:
        """A serve-loop error must not strand clients on result(): mark
        every live request failed and wake its waiters."""
        xfa.count_event("serve", "engine_error")
        with self._lock:
            self._error = exc
            live = [s.request for s in self.scheduler.slots
                    if s.request is not None]
            live += list(self.scheduler.waiting)
            self.scheduler.waiting.clear()
            if self.paged:
                # recycle every page and reservation so a post-mortem
                # reading the allocator sees the true terminal state
                for req in live:
                    self.allocator.release(req.uid)
                self.block_tables[:] = 0
            for i in self.scheduler.active():
                self.scheduler.release(i)
            for req in live:
                req.error = exc
                req._done_event.set()
            self._stop = True

    @property
    def error(self) -> Optional[BaseException]:
        """The failure that stopped the serve loop, if any."""
        return self._error

    # -- profiling ----------------------------------------------------------
    def write_profile_shard(self) -> None:
        """Refresh this replica's profile shard (host tracer folds)."""
        if self._profile_store is None:
            return
        from repro.profile import tracer_folded
        self._profile_store.write_shard(
            tracer_folded(), label=self.scfg.profile_label,
            meta={"ticks": self._ticks, "completed": len(self.completed)})
        if self._publisher is not None:
            # local ring first, then the delta stream; publish() never
            # raises — a dead collector degrades to local-only profiling
            with xfa.scope("serve", "profile_publish"):
                self._publisher.publish()

    # -- synchronous driver -------------------------------------------------
    def run_until_drained(self, max_ticks: int = 10_000) -> List[Request]:
        """Serve until queue and pool are empty.  With a background thread
        running this just waits for quiescence; otherwise it drives the
        loop inline (closed-loop mode)."""
        t = self._thread
        if t is not None and t.is_alive():
            deadline = time.monotonic() + max_ticks * 0.1
            while True:
                # observe under the engine lock: step() holds it across
                # pop -> bind -> tick, so a request mid-admission can
                # never look like "neither waiting nor active" from here
                with self._lock:
                    if not self.scheduler.has_work():
                        break
                if time.monotonic() > deadline:
                    break
                time.sleep(0.002)
            return self.completed
        for _ in range(max_ticks):
            n = self.step()
            if n == 0 and not self.scheduler.has_waiting():
                break
        self.write_profile_shard()
        return self.completed
