"""Share of the traced window in which no operation ran on the device."""
from bench.metrics import _serve


def read(run):
    return _serve.idle_pct(run)
