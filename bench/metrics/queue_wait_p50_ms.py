"""Median wait from a request's due time to its admission to a slot
(the program's Request.admitted_at), over the window's requests."""
from bench import stats
from bench.metrics import _serve


def read(run):
    v = [(r["admitted"] - r["due"]) * 1e3 for r in _serve.in_window(run)
         if r["admitted"] is not None]
    return stats.percentile(v, 50) if v else None
