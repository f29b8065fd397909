"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own counts: what a decoder layer, a serving
step and each attention kernel must do at least, independent of how the
program does it.  A roofline share is the least time these counts allow
(the larger of operations over peak FLOP/s and bytes over peak
bandwidth) divided by the time the trace measured.

Layers are counted by kind: the leading dense layers (attention and the
dense MLP), then, where the model has experts, the MoE layers
(attention, the router and the top_k routed plus the shared experts a
token passes through, each a gated MLP of width moe_d_ff).  Attention
is GQA over head_dim, or MLA (DeepSeek-V2, arXiv:2405.04434 section 2.1)
in one of its two forms:

- latent, as serving runs it: the paged cache holds one latent row of
  r + dr per position (c_kv and the shared rope key), the key
  projection is absorbed into the query and the value projection into
  the output, so a head scores over r + dr and sums values over r;
- expanded, as training runs it: K and V are expanded per head from the
  latent, so a head scores over dn + dr and sums values over dv.

The projections (q, the latent down-projection, its up-projection to
the heads' keys and values, the output) count the same in both forms.
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
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0
    mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    @classmethod
    def of(cls, program: dict) -> "Dims":
        """From the program's `ModelConfig` fields, with its defaults:
        head_dim 0 is d_model / n_heads, the dense MLP is gated unless
        mlp_gated is false, and experts count only where moe is set."""
        p = program
        moe = p.get("moe", False)
        return cls(layers=p["n_layers"], d_model=p["d_model"],
                   heads=p["n_heads"], kv_heads=p["n_kv_heads"],
                   head_dim=p.get("head_dim") or p["d_model"] // p["n_heads"],
                   d_ff=p["d_ff"], vocab=p["vocab"],
                   gated=p.get("mlp_gated", True),
                   n_experts=p.get("n_experts", 0) if moe else 0,
                   top_k=p.get("top_k", 0),
                   n_shared_experts=p.get("n_shared_experts", 0),
                   moe_d_ff=p.get("moe_d_ff", 0),
                   first_dense_layers=p.get("first_dense_layers", 0),
                   mla=p.get("mla", False),
                   kv_lora_rank=p.get("kv_lora_rank", 0),
                   qk_nope_dim=p.get("qk_nope_dim", 0),
                   qk_rope_dim=p.get("qk_rope_dim", 0),
                   v_head_dim=p.get("v_head_dim", 0))

    @property
    def moe_layers(self) -> int:
        return self.layers - self.first_dense_layers if self.n_experts else 0

    @property
    def attn_params(self) -> int:
        """Attention projection weights one token multiplies through."""
        d, H = self.d_model, self.heads
        if self.mla:
            r, dn, dr, dv = (self.kv_lora_rank, self.qk_nope_dim,
                             self.qk_rope_dim, self.v_head_dim)
            return (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
                    + H * dv * d)
        return d * self.head_dim * (2 * H + 2 * self.kv_heads)

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one dense layer."""
        return self.attn_params + (3 if self.gated else 2) * self.d_model \
            * self.d_ff

    @property
    def moe_layer_params(self) -> int:
        """Weights one token multiplies through in one MoE layer: the
        router, and its top_k routed and the shared experts."""
        d = self.d_model
        return (self.attn_params + d * self.n_experts
                + (self.top_k + self.n_shared_experts) * 3 * d * self.moe_d_ff)

    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through in all layers."""
        return ((self.layers - self.moe_layers) * self.layer_matmul_params
                + self.moe_layers * self.moe_layer_params)

    def pair_widths(self, latent: bool) -> Tuple[int, int]:
        """(score width, value width) of one head against one key: MLA's
        latent row (r + dr, r) where `latent`, else its expanded head
        (dn + dr, dv); head_dim for both without MLA."""
        if not self.mla:
            return self.head_dim, self.head_dim
        if latent:
            return self.kv_lora_rank + self.qk_rope_dim, self.kv_lora_rank
        return self.qk_nope_dim + self.qk_rope_dim, self.v_head_dim

    @property
    def cached_row_bytes(self) -> int:
        """Bytes of the cache one position holds in one layer: K and V of
        every kv head, or MLA's one latent row, both key and value."""
        if self.mla:
            return (self.kv_lora_rank + self.qk_rope_dim) * BF16
        return 2 * self.kv_heads * self.head_dim * BF16


def attn_ctx_flops(dims: Dims, ctx_sum: float, latent: bool = False
                   ) -> float:
    """Score and value FLOPs of one layer's attention: 2 * Hq * (score
    width + value width) per (query, visible key) pair, ctx_sum being
    the number of such pairs; `latent` takes MLA's serving form."""
    qk, v = dims.pair_widths(latent)
    return 2.0 * dims.heads * (qk + v) * ctx_sum


def chunk_ctx_sum(pos: int, n: int) -> float:
    """Visible (query, key) pairs of n queries at offsets pos..pos+n-1
    under the offset-causal mask: sum of (pos + t + 1)."""
    return n * pos + n * (n + 1) / 2.0


def serve_step_flops(dims: Dims, rows: Iterable[Tuple[int, int]]) -> float:
    """One forward_chunk call: rows are (pos, n_valid) for every real row
    (bucket pad rows and pad tokens excluded).  Each row takes the logits
    of its last valid token only.  MLA attention in its latent form."""
    total = 0.0
    for pos, n in rows:
        if n <= 0:
            continue
        total += 2.0 * n * dims.matmul_params
        total += 2.0 * dims.d_model * dims.vocab
        total += dims.layers * attn_ctx_flops(dims, chunk_ctx_sum(pos, n),
                                              latent=True)
    return total


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward (3x forward) FLOPs per trained token, causal
    attention over seq_len; rematerialised work is not counted.  MLA
    attention in its expanded form."""
    matmul = 2.0 * (dims.matmul_params + dims.d_model * dims.vocab)
    attn = dims.layers * attn_ctx_flops(dims, (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -- kernels: (flops, bytes) of one call ------------------------------------
def decode_attn_cost(dims: Dims, kv_lens: Iterable[int]) -> Tuple[float, float]:
    """Paged decode attention, one layer: one query per live row against
    its kv_len cached rows.  Bytes: the live cached rows, q and o (MLA:
    latent rows, q and o in latent space)."""
    qk, v = dims.pair_widths(latent=True)
    fl = by = 0.0
    for n in kv_lens:
        fl += attn_ctx_flops(dims, n, latent=True)
        by += n * dims.cached_row_bytes
        by += dims.heads * (qk + v) * BF16
    return fl, by


def chunk_attn_cost(dims: Dims, rows: Iterable[Tuple[int, int]]
                    ) -> Tuple[float, float]:
    """Paged chunk attention, one layer: rows are (pos, n_valid).  Bytes:
    the visible cached prefix (pos + n rows), the n queries and outputs
    (MLA: latent rows, queries and outputs in latent space)."""
    qk, v = dims.pair_widths(latent=True)
    fl = by = 0.0
    for pos, n in rows:
        if n <= 0:
            continue
        fl += attn_ctx_flops(dims, chunk_ctx_sum(pos, n), latent=True)
        by += (pos + n) * dims.cached_row_bytes
        by += n * dims.heads * (qk + v) * BF16
    return fl, by


def flash_attn_cost(dims: Dims, batch: int, seq_len: int
                    ) -> Tuple[float, float]:
    """Causal flash attention forward, one layer: q, k, v, o and the f32
    log-sum-exp per query row (MLA: its expanded heads)."""
    qk, v = dims.pair_widths(latent=False)
    fl = batch * attn_ctx_flops(dims, chunk_ctx_sum(0, seq_len))
    by = batch * seq_len * BF16 * (dims.heads + dims.kv_heads) * (qk + v)
    by += batch * dims.heads * seq_len * 4
    return fl, by


def expert_ffn_cost(dims: Dims, tokens_per_expert: Iterable[int]
                    ) -> Tuple[float, float]:
    """The routed experts of one MoE layer, n_e tokens on expert e: each
    token through a gated MLP of width moe_d_ff.  Bytes: the three
    matrices of every expert that has a token, and each token's input
    and output rows."""
    d, f = dims.d_model, dims.moe_d_ff
    fl = by = 0.0
    for n in tokens_per_expert:
        if n <= 0:
            continue
        fl += n * 6.0 * d * f
        by += 3 * d * f * BF16 + n * 2 * d * BF16
    return fl, by


def min_time(cost: Tuple[float, float], peaks: dict) -> Tuple[float, str]:
    """(least seconds, bound) for (flops, bytes) on a chip."""
    fl, by = cost
    tc, tm = fl / peaks["bf16_flops"], by / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
