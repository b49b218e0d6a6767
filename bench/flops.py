"""Operations and bytes the work needs, counted from shapes.

``train_flops_per_token``: model FLOPs of one training token of a dense
decoder, forward plus backward (backward = 2 x forward), counting every
matrix product (the four attention projections, the SwiGLU MLP, the tied
output head) and the two attention products. Attention convention: causal,
so a query at position i attends to i + 1 keys, (S + 1) / 2 on average;
the masked half of the score matrix that an unfused implementation also
computes is not counted. Recomputation (remat) is not counted. Norms,
rotary, softmax and the loss are not counted (each is under 0.1% of the
matrix products at these widths). One multiply-add is 2 FLOPs.

``update_bytes``: the least HBM traffic of one fused optimizer sweep over
parameter buckets: each bucket's parameters, gradients and moments read,
parameters and moments written, and the partner's parameters read when the
gossip mix is on (alpha != 0).
"""
from __future__ import annotations

from typing import Sequence


def train_flops_per_token(*, d: int, layers: int, heads: int, kv_heads: int,
                          head_dim: int, ff: int, vocab: int,
                          seq_len: int) -> float:
    proj = 2 * d * heads * head_dim            # q
    proj += 2 * 2 * d * kv_heads * head_dim    # k, v
    proj += 2 * heads * head_dim * d           # o
    mlp = 3 * 2 * d * ff                       # gate, up, down
    ctx = (seq_len + 1) / 2                    # causal keys per query
    attn = 2 * 2 * heads * head_dim * ctx      # q k^T and p v
    head = 2 * d * vocab
    forward = layers * (proj + mlp + attn) + head
    return 3.0 * forward


def update_bytes(bucket_elems: Sequence[int], *, param_bytes: int,
                 grad_bytes: int, moment_bytes: Sequence[int],
                 partner: bool) -> int:
    """Bytes one sweep over the buckets moves, per replica."""
    per_elem = 2 * param_bytes + grad_bytes + 2 * sum(moment_bytes)
    if partner:
        per_elem += param_bytes
    return int(sum(bucket_elems)) * per_elem
