"""Operations a token requires in a Llama-shaped block (grouped-query
attention, SwiGLU), from a configuration file's published keys."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product: the projections, the
    feed-forward and the output head. The input embedding is a lookup and
    the norm scales are element-wise; neither counts."""
    D, L = model["hidden_size"], model["num_hidden_layers"]
    H, K = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or D // H
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * model["intermediate_size"]
    return L * per_layer + D * model["vocab_size"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations the forward and backward passes require per token:
    6 per matrix-product parameter, plus causal attention's two products
    (scores and values) at 6 * L * S * (H * hd): 4 * S * H * hd forward over
    the full square, halved by the causal mask, times 3 for the backward.
    Recomputation is not counted."""
    H = model["num_attention_heads"]
    hd = model.get("head_dim") or model["hidden_size"] // H
    return 6.0 * matmul_params(model) + 6.0 * model["num_hidden_layers"] * seq_len * H * hd
