"""90th percentile time to first token, from each request's due time,
over every request due in the window (missing ones count as late)."""
from bench import stats
from bench.metrics import _serve


def read(run):
    v = _serve.ttfts_ms(run)
    return stats.percentile(v, 90) if v else None
