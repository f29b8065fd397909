"""xfa_chunk_attention_paged: least time the valid rows' work allows
over its traced time."""
from bench import flops
from bench.metrics import _serve


def read(run):
    return _serve.roofline(run, "xfa_chunk_attention_paged", "chunk",
                           flops.chunk_attn_cost)
