"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives a whole run of
a test cell on the CPU with one fault planted in the program: a token
altered where it is produced, a prefill that leaves the cache
unchanged, a train step that returns its state unchanged, and a train
step that leaves half of the batch out and takes the mean over the
rest.  A sound run of each cell is correct.  (One chip: there is no
exchange between chips to leave out.)
"""

import dataclasses

import pytest

from bench.tests import _tiny


def test_sound_runs_are_correct():
    for cell in ("tiny.chat", "tiny.train"):
        r = _tiny.run_cell(cell, 2 ** 31 + 17)
        assert r["correct"], (cell, r["checked"])
        assert r["attempted"] > 0 and r["failed"] == 0
        assert list(r["checked"])[-1] is not None
        assert list(r)[-1] == "checked"


def test_token_altered_where_produced(monkeypatch):
    from repro.serving.sampling import PooledSampler
    call, one = PooledSampler.__call__, PooledSampler.sample_one
    monkeypatch.setattr(PooledSampler, "__call__",
                        lambda s, lg, step: (call(s, lg, step) + 1) % 512)
    monkeypatch.setattr(PooledSampler, "sample_one",
                        lambda s, row, sp, step: (one(s, row, sp, step)
                                                  + 1) % 512)
    r = _tiny.run_cell("tiny.chat", 11)
    assert not r["correct"], r["checked"]


def test_prefill_leaves_cache_unchanged(monkeypatch):
    import repro.models as models
    build = models.build_model

    def broken(cfg, impl="auto"):
        m = build(cfg, impl)
        fwd = m.forward_chunk_paged

        def chunk(params, tokens, table, cache, pos, bt, valid=None):
            logits, _, table = fwd(params, tokens, table, cache, pos, bt,
                                   valid)
            return logits, cache, table
        return dataclasses.replace(m, forward_chunk_paged=chunk)
    monkeypatch.setattr(models, "build_model", broken)
    r = _tiny.run_cell("tiny.chat", 12)
    assert not r["correct"], r["checked"]


def _broken_step(monkeypatch, wrap):
    import repro.runtime.trainer as trainer
    make = trainer.make_train_step
    monkeypatch.setattr(trainer, "make_train_step",
                        lambda model, tcfg: wrap(make(model, tcfg)))


def test_train_step_returns_state_unchanged(monkeypatch):
    def wrap(step):
        def f(state, batch, table):
            _, metrics, table = step(state, batch, table)
            return state, metrics, table
        return f
    _broken_step(monkeypatch, wrap)
    r = _tiny.run_cell("tiny.train", 13)
    assert not r["correct"], r["checked"]
    assert r["checked"]["change_gap"]["value"] == pytest.approx(1.0)


def test_train_step_leaves_half_the_batch_out(monkeypatch):
    def wrap(step):
        def f(state, batch, table):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half, table)
        return f
    _broken_step(monkeypatch, wrap)
    r = _tiny.run_cell("tiny.train", 14)
    assert not r["correct"], r["checked"]
