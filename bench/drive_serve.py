"""Serving cells: drive the program's ServingEngine from one thread.

Set-up builds the engine from the configuration with weights from the
seed, and compiles and runs once every program the cell's traffic can
schedule.  The window then
submits each request when it is due and calls `engine.step()` whenever
there is work: one process, one thread, no background serving loop.
Time to first token is taken from when a request was DUE, so a step
that holds the loop up delays every request due behind it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic, weights
from bench.tracing import Recorder


@dataclasses.dataclass
class Served:
    """What the window saw of one request (host clock, seconds)."""
    req: traffic.Req
    handle: object = None                 # the engine's Request
    submit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    due_t0: float = 0.0                   # when the window opened

    @property
    def due_abs(self) -> float:
        return self.due_t0 + self.req.due


def build(cfg: dict, seed: int, model=None):
    """The engine with the seed's weights; pass `model` to reuse one
    model (and so its compiled programs) across seeds in one process."""
    from repro.configs.base import ModelConfig, ServeConfig
    from repro.models import build_model
    from repro.serving.engine import ServingEngine
    if model is None:
        model = build_model(ModelConfig(**cfg["program"]))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make_params(shapes, seed)
    return ServingEngine(model, params, ServeConfig(**cfg["serve"]))


def warm(engine) -> None:
    """Compile and run, outside the window, every program the engine can
    schedule for this configuration: each (group batch, chunk width)
    prefill program against the live arena (an all-zero block table
    writes only the scratch page), the pooled decode tick, the sampler
    at both of its widths, and the row reads of each group's logits."""
    from repro.serving.sampling import GREEDY
    e = engine
    nb = e._n_blocks
    for w in e.chunk_buckets():
        for b in e.batch_buckets():
            logits, e.cache, e.table = e._chunk(
                e.params, jnp.zeros((b, w), jnp.int32), e.table, e.cache,
                jnp.zeros((b,), jnp.int32), jnp.zeros((b, nb), jnp.int32),
                jnp.ones((b,), jnp.int32))
            rows = [np.asarray(logits[r]) for r in range(b)]
            e.sampler.sample_one(rows[0], GREEDY, step=1)
    B = e.scfg.max_batch
    logits, e.cache, e.table = e._decode(
        e.params, jnp.zeros((B,), jnp.int32), e.table, e.cache,
        jnp.zeros((B,), jnp.int32), jnp.asarray(e.block_tables))
    e.sampler(logits, step=np.ones((B,), np.int32))


def _submit(engine, s: Served, now: float) -> None:
    times = s.times
    s.submit = now
    s.handle = engine.submit(
        s.req.prompt, max_new_tokens=s.req.max_new,
        on_token=lambda _r, _t: times.append(time.monotonic()))


def open_loop(engine, reqs: List[traffic.Req], seconds: float, drain: float,
              rec: Recorder, finished: int = 0, tokens: int = 0) -> dict:
    """Submit each request at its due time and step the engine until
    every request due in the window has its first token (or `drain`
    seconds past the window); then step on, submitting nothing, until
    `finished` requests holding `tokens` served tokens are done, so the
    check has answers to read."""
    served = [Served(r) for r in sorted(reqs, key=lambda r: r.due)]
    win = [s for s in served if s.req.in_window]
    t0 = time.monotonic()
    for s in served:
        s.due_t0 = t0
    i, n = 0, len(served)
    sched = engine.scheduler
    while True:
        now = time.monotonic()
        el = now - t0
        while i < n and served[i].req.due <= el:
            _submit(engine, served[i], now)
            i += 1
        if el >= seconds and all(s.times or (s.handle and s.handle.error)
                                 for s in win):
            break
        if el >= seconds + drain:
            break
        rec.tick(el)
        if sched.has_work():
            rec.step(engine.step)
        else:
            wait = served[i].req.due - el if i < n else 0.001
            time.sleep(min(max(wait, 0.0), 0.002))
    rec.stop()
    t_end = time.monotonic()
    _finish_some(engine, served, finished, tokens, t_end + drain)
    return {"served": served, "t0": t0, "t_end": t_end}


def _finish_some(engine, served, finished: int, tokens: int,
                 deadline: float) -> None:
    """Step on, without new submissions, until `finished` requests with
    `tokens` served tokens in all are done, or the deadline passes."""
    def enough():
        done = [s for s in served if s.handle is not None and s.handle.done]
        return (len(done) >= finished
                and sum(len(s.handle.output) for s in done) >= tokens)
    while not enough() and engine.scheduler.has_work() \
            and time.monotonic() < deadline:
        engine.step()
