"""Operations and bytes the model's work needs, counted from its shapes.

The counts are the model's own (2*M*K*N per matmul, plus attention),
whatever implements the products: the AMR emulation's extra lanes and
lookups are never counted, so a change that removes emulation work raises
a utilization and one that swaps a kernel leaves the count as it was.
Attention is counted over the whole context (the PaLM convention,
12 * layers * heads * head_dim * context per trained token).
"""
from __future__ import annotations


def weight_matmuls(c: dict) -> list[tuple[int, int]]:
    """(K, N) of the weight matmuls of one layer that run through the
    numerics seam: q, k, v, o projections, MLP gate, up and down."""
    d, hd = c["d_model"], c["head_dim"]
    return [(d, c["n_heads"] * hd), (d, c["n_kv_heads"] * hd),
            (d, c["n_kv_heads"] * hd), (c["n_heads"] * hd, d),
            (d, c["d_ff"]), (d, c["d_ff"]), (c["d_ff"], d)]


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matmul: every layer's weights and the
    LM head (the embedding lookup does no arithmetic)."""
    per_layer = sum(k * n for k, n in weight_matmuls(c))
    return c["n_layers"] * per_layer + c["vocab"] * c["d_model"]


def attention_flops_per_token(c: dict, context: int) -> int:
    """Forward QK^T and PV of one token against ``context`` positions."""
    return 4 * c["n_layers"] * c["n_heads"] * c["head_dim"] * context


def train_flops_per_token(c: dict, seq: int) -> int:
    """Forward and backward: 6 per parameter, 3x the forward attention."""
    return 6 * matmul_params(c) + 3 * attention_flops_per_token(c, seq)


def decode_flops(c: dict, context: int) -> int:
    """Forward of one generated token that attends ``context`` positions."""
    return 2 * matmul_params(c) + attention_flops_per_token(c, context)

