"""xfa_decode_attention_paged: least time its live rows' work allows
(the larger of FLOPs over peak and bytes over bandwidth) over its
traced time."""
from bench import flops
from bench.metrics import _serve


def read(run):
    return _serve.roofline(
        run, "xfa_decode_attention_paged", "decode",
        lambda dims, rows: flops.decode_attn_cost(dims,
                                                  [p + 1 for p, _ in rows]))
