"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own counts: what a dense decoder layer, a
serving step and each attention kernel must do at least, independent of
how the program does it.  A roofline share is the least time these
counts allow (the larger of operations over peak FLOP/s and bytes over
peak bandwidth) divided by the time the trace measured.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool = False

    @classmethod
    def of(cls, program: dict) -> "Dims":
        p = program
        return cls(layers=p["n_layers"], d_model=p["d_model"],
                   heads=p["n_heads"], kv_heads=p["n_kv_heads"],
                   head_dim=p["head_dim"], d_ff=p["d_ff"], vocab=p["vocab"],
                   gated=p.get("mlp_gated", False))

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer."""
        d, h = self.d_model, self.head_dim
        attn = d * h * (2 * self.heads + 2 * self.kv_heads)
        mlp = (3 if self.gated else 2) * d * self.d_ff
        return attn + mlp


def attn_ctx_flops(dims: Dims, ctx_sum: float) -> float:
    """Score and value FLOPs of one layer's attention: 4 * Hq * D per
    (query, visible key) pair, ctx_sum being the number of such pairs."""
    return 4.0 * dims.heads * dims.head_dim * ctx_sum


def chunk_ctx_sum(pos: int, n: int) -> float:
    """Visible (query, key) pairs of n queries at offsets pos..pos+n-1
    under the offset-causal mask: sum of (pos + t + 1)."""
    return n * pos + n * (n + 1) / 2.0


def serve_step_flops(dims: Dims, rows: Iterable[Tuple[int, int]]) -> float:
    """One forward_chunk call: rows are (pos, n_valid) for every real row
    (bucket pad rows and pad tokens excluded).  Each row takes the logits
    of its last valid token only."""
    total = 0.0
    for pos, n in rows:
        if n <= 0:
            continue
        total += 2.0 * n * dims.layers * dims.layer_matmul_params
        total += 2.0 * dims.d_model * dims.vocab
        total += dims.layers * attn_ctx_flops(dims, chunk_ctx_sum(pos, n))
    return total


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward (3x forward) FLOPs per trained token, causal
    attention over seq_len; rematerialised work is not counted."""
    matmul = 2.0 * (dims.layers * dims.layer_matmul_params
                    + dims.d_model * dims.vocab)
    attn = dims.layers * attn_ctx_flops(dims, (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -- kernels: (flops, bytes) of one call ------------------------------------
def decode_attn_cost(dims: Dims, kv_lens: Iterable[int]) -> Tuple[float, float]:
    """Paged decode attention, one layer: one query per live row against
    its kv_len cached rows.  Bytes: the live K/V rows, q and o."""
    fl = by = 0.0
    for n in kv_lens:
        fl += attn_ctx_flops(dims, n)
        by += 2.0 * dims.kv_heads * n * dims.head_dim * BF16
        by += 2.0 * dims.heads * dims.head_dim * BF16
    return fl, by


def chunk_attn_cost(dims: Dims, rows: Iterable[Tuple[int, int]]
                    ) -> Tuple[float, float]:
    """Paged chunk attention, one layer: rows are (pos, n_valid).  Bytes:
    the visible K/V prefix (pos + n rows), the n queries and outputs."""
    fl = by = 0.0
    for pos, n in rows:
        if n <= 0:
            continue
        fl += attn_ctx_flops(dims, chunk_ctx_sum(pos, n))
        by += 2.0 * dims.kv_heads * (pos + n) * dims.head_dim * BF16
        by += 2.0 * n * dims.heads * dims.head_dim * BF16
    return fl, by


def flash_attn_cost(dims: Dims, batch: int, seq_len: int
                    ) -> Tuple[float, float]:
    """Causal flash attention forward, one layer: q, k, v, o and the f32
    log-sum-exp per query row."""
    fl = batch * attn_ctx_flops(dims, chunk_ctx_sum(0, seq_len))
    by = batch * seq_len * dims.head_dim * BF16 * (2 * dims.heads
                                                   + 2 * dims.kv_heads)
    by += batch * dims.heads * seq_len * 4
    return fl, by


def min_time(cost: Tuple[float, float], peaks: dict) -> Tuple[float, str]:
    """(least seconds, bound) for (flops, bytes) on a chip."""
    fl, by = cost
    tc, tm = fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
