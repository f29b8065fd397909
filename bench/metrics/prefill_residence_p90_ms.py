"""Nearest-rank p90, over the window's requests, of admission to first
token: the program's Request.admitted_at to its first `on_token` call,
which the engine makes at Request.first_token_at."""
from bench import stats
from bench.metrics import _serve


def read(run):
    v = [(r["times"][0] - r["admitted"]) * 1e3 for r in _serve.in_window(run)
         if r["admitted"] is not None and r["times"]]
    return stats.percentile(v, 90) if v else None
