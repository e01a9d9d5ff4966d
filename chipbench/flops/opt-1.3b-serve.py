"""Operations and least bytes of the served decoder from its shapes.

Matmul parameters per token: 4 d^2 (q, k, v, o) + 2 d f per layer, plus
the output head d V.  Attention: a token at context length c multiplies
its query with c keys and its weights with c values: 2 * 2 * d * c
FLOPs per layer.  The decode step's least bytes are the K and V of the
live context of every active row (what paged attention must read)."""

from __future__ import annotations


def matmul_params(sizes) -> float:
    d, f = int(sizes["hidden_size"]), int(sizes["ffn_dim"])
    layers, v = int(sizes["num_hidden_layers"]), int(sizes["vocab_size"])
    return float(layers * (4 * d * d + 2 * d * f) + d * v)


def token_flops(sizes, context: float) -> float:
    """FLOPs of one token whose attention spans ``context`` positions."""
    d, layers = int(sizes["hidden_size"]), int(sizes["num_hidden_layers"])
    return 2.0 * matmul_params(sizes) + 4.0 * layers * d * context


def prefill_flops(sizes, prompt: int) -> float:
    """All prompt tokens through the layers, the output head on the
    last one only (it alone gives a token)."""
    d, layers = int(sizes["hidden_size"]), int(sizes["num_hidden_layers"])
    head = float(d * int(sizes["vocab_size"]))
    return (2.0 * (matmul_params(sizes) - head) * prompt + 2.0 * head
            + 4.0 * layers * d * prompt * (prompt + 1) / 2.0)


def kv_bytes_per_token(sizes, dtype_bytes: int = 4) -> float:
    """K and V of one cached token over all layers."""
    return 2.0 * int(sizes["num_hidden_layers"]) \
        * int(sizes["hidden_size"]) * dtype_bytes


def decode_attention_flops(sizes, context: float) -> float:
    return 4.0 * int(sizes["num_hidden_layers"]) \
        * int(sizes["hidden_size"]) * context
