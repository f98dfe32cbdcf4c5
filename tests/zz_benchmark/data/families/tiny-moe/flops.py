"""Operations a token requires in the made-up ``tiny-moe`` block: the
``llama`` family's attention and head, the router, and the experts a token is
sent to (two products each; the experts it is not sent to do not count)."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    D, L = model["hidden_size"], model["num_hidden_layers"]
    H, K = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or D // H
    experts = model["num_experts_per_tok"] * 2 * D * model["intermediate_size"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + D * model["num_local_experts"] + experts
    return L * per_layer + D * model["vocab_size"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    H = model["num_attention_heads"]
    hd = model.get("head_dim") or model["hidden_size"] // H
    return 6.0 * matmul_params(model) + 6.0 * model["num_hidden_layers"] * seq_len * H * hd
