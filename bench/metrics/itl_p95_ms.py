"""95th percentile gap between consecutive tokens, pooled over every
request due in the window."""
from bench import stats
from bench.metrics import _serve


def read(run):
    v = _serve.token_gaps_ms(run)
    return stats.percentile(v, 95) if v else None
