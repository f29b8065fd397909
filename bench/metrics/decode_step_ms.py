"""Device time per decode-tick program in the traced window."""
from bench.metrics import _serve


def read(run):
    n, s = _serve.module_seconds(run, "decode_step_paged")
    return 1e3 * s / n if n else None
