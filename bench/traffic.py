"""The one traffic generator: reads a mix file of parameters
(`bench/traffic/<mix>.json`) and makes a run's requests or training
job from `--seed`.

Steadiness rule: every seed gets the SAME sizes and inter-arrival gaps,
in the same order, drawn once from the mix's own `base_seed`; the run's
seed draws the token ids (and the check's sample).  Runs with different
seeds then do the same work, so the spread between seeds measures the
system, not the draw: with about fifty requests in a window, a tail
would otherwise swing with where the few longest requests land.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

@dataclasses.dataclass
class Req:
    """One request as the generator makes it."""
    index: int
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int
    in_window: bool


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths from a clipped lognormal (median, sigma, min, max)."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _sizes(mix: dict, n: int, salt: int) -> tuple:
    rng = np.random.default_rng([mix["base_seed"], salt])
    return _lengths(mix["prompt"], n, rng), _lengths(mix["output"], n, rng)


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """The run's own generator for one purpose (ids, sample)."""
    return np.random.default_rng([seed, stream])


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """Poisson arrivals at mix['rate_per_s'] for `seconds`, then the same
    rate for mix['drain_s'] more, so load stays on while the last
    requests of the window are answered.  The window's gaps are scaled
    to sum to exactly `seconds`, so every seed offers the same load."""
    rate = mix["rate_per_s"]
    n_win = max(1, round(rate * seconds))
    n_after = math.ceil(rate * mix["drain_s"])
    base = np.random.default_rng([mix["base_seed"], 1])
    gaps_win = base.exponential(1.0, n_win)
    gaps_win *= seconds / gaps_win.sum()
    gaps_after = base.exponential(1.0 / rate, n_after)
    p_win, o_win = _sizes(mix, n_win, 2)
    p_aft, o_aft = _sizes(mix, n_after, 3)
    # the first request is due when the window opens
    due_win = np.concatenate([[0.0], np.cumsum(gaps_win)[:-1]])
    due_aft = seconds + np.cumsum(gaps_after) - gaps_after[0]
    ids = run_rng(seed, 1)
    out = []
    for k, (due, p, o) in enumerate(zip(
            np.concatenate([due_win, due_aft]),
            np.concatenate([p_win, p_aft]), np.concatenate([o_win, o_aft]))):
        out.append(Req(k, float(due),
                       ids.integers(0, vocab, int(p), dtype=np.int32),
                       int(o), k < n_win))
    return out


def check_sample(done: List[Req], served: dict, seed: int, n: int,
                 min_tokens: int) -> List[Req]:
    """The requests whose output the reference checks: the finished
    request with the most served tokens, then others drawn from the
    seed until `n` requests or `min_tokens` served tokens are reached."""
    if not done:
        return []
    longest = max(done, key=lambda r: (len(served[r.index]), r.index))
    rest = [r for r in sorted(done, key=lambda r: r.index) if r is not longest]
    rest = [rest[i] for i in run_rng(seed, 2).permutation(len(rest))]
    out, tokens = [longest], len(served[longest.index])
    for r in rest:
        if len(out) >= n and tokens >= min_tokens:
            break
        out.append(r)
        tokens += len(served[r.index])
    return out


def train_seed(seed: int) -> int:
    """The data pipeline's seed: it shifts the seed left by 40 bits, so
    keep it within 23 bits."""
    return seed % (1 << 23)
