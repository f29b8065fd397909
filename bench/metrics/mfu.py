"""FLOPs the traced engine steps needed (from their shapes) over the
traced window times the chip's bf16 peak."""
from bench.metrics import _serve


def read(run):
    return _serve.mfu(run)
