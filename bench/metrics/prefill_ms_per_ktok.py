"""Device time of the prefill-chunk programs per thousand prompt tokens
they processed, in the traced window."""
from bench.metrics import _serve


def read(run):
    n, s = _serve.module_seconds(run, "forward_chunk_paged")
    toks = sum(b for c in _serve.calls(run, "chunk") for _, b in c["rows"])
    return 1e6 * s / toks if n and toks else None
