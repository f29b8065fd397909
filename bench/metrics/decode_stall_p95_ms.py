"""p95 of the program's `serve.decode_stall` histogram: from the start
of an engine step to its decode call's dispatch, once per row decoding
at the step's start (so weighted by tokens, as `itl_p95_ms` is)."""
from bench.metrics import _fold


def read(run):
    e = _fold.edge("decode_stall")
    return None if e is None or e.hist is None else e.p95_ns * 1e-6
