"""Operations and least bytes of the served Trinity-Mini decoder from
its shapes, as ``configs/trinity-mini-serve.json`` runs it.

Matmul parameters a token really passes (the **active** ones): per layer
the attention's q, gate and output projections (3 d H D) and k, v
(2 d G D); a dense layer's SwiGLU (3 d f); a routed layer's router
(d E), its ``num_experts_per_tok`` routed and ``num_shared_experts``
shared experts (3 d f_e each); the output head d V.  Attention: a token
whose context holds c positions multiplies its query with the keys it
sees and its weights with their values: 4 H D FLOPs a visible position
and layer, and a sliding layer sees min(c, W) of them."""

from __future__ import annotations


def _dims(sizes):
    return (int(sizes["hidden_size"]),
            int(sizes["num_attention_heads"]) * int(sizes["head_dim"]),
            int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]))


def _sliding(sizes):
    n = sum(t == "sliding_attention" for t in sizes["layer_types"])
    return n, int(sizes["num_hidden_layers"]) - n


def expert_params(sizes) -> float:
    """One expert: gate, up and down."""
    return 3.0 * int(sizes["hidden_size"]) \
        * int(sizes["moe_intermediate_size"])


def layer_matmul_params(sizes) -> float:
    """Active matmul parameters of all layers (no head)."""
    d, hd, gd = _dims(sizes)
    layers, dense = int(sizes["num_hidden_layers"]), \
        int(sizes["num_dense_layers"])
    attn = 3.0 * d * hd + 2.0 * d * gd
    routed = d * int(sizes["num_experts"]) + expert_params(sizes) * (
        int(sizes["num_experts_per_tok"])
        + int(sizes["num_shared_experts"]))
    return layers * attn + dense * 3.0 * d * int(
        sizes["intermediate_size"]) + (layers - dense) * routed


def head_params(sizes) -> float:
    return float(int(sizes["hidden_size"]) * int(sizes["vocab_size"]))


def attention_flops(sizes, context: float) -> float:
    """Attention FLOPs of one token whose context (itself included)
    holds ``context`` positions, over all layers."""
    _, hd, _ = _dims(sizes)
    sliding, full = _sliding(sizes)
    seen = min(context, float(sizes["sliding_window"]))
    return 4.0 * hd * (sliding * seen + full * context)


def token_flops(sizes, context: float) -> float:
    """FLOPs of one token whose attention spans ``context`` positions."""
    return 2.0 * (layer_matmul_params(sizes) + head_params(sizes)) \
        + attention_flops(sizes, context)


def prefill_flops(sizes, prompt: int) -> float:
    """All prompt tokens through the layers, the output head on the
    last one only (it alone gives a token).  Token i sees i + 1
    positions on a full layer and min(i + 1, W) on a sliding one."""
    _, hd, _ = _dims(sizes)
    sliding, full = _sliding(sizes)
    w = min(int(sizes["sliding_window"]), prompt)
    seen_full = prompt * (prompt + 1) / 2.0
    seen_sliding = w * (w + 1) / 2.0 + (prompt - w) * w
    return 2.0 * layer_matmul_params(sizes) * prompt \
        + 2.0 * head_params(sizes) \
        + 4.0 * hd * (sliding * seen_sliding + full * seen_full)


# ------------------------------------------------ per-unit work of a span
def layer_kv_bytes_per_token(sizes, dtype_bytes: int = 2) -> float:
    """K and V of one cached position in ONE layer (the unit of the
    decode span's ``attended_tokens``)."""
    return 2.0 * _dims(sizes)[2] * dtype_bytes


def layer_attention_flops(sizes, positions: float) -> float:
    """Decode attention over ``positions`` attended positions of one
    layer."""
    return 4.0 * _dims(sizes)[1] * positions


def expert_bytes(sizes, dtype_bytes: int = 2) -> float:
    """The weights of one expert (the unit of the decode span's
    ``experts_hit``: what a step must read of an expert it hits)."""
    return expert_params(sizes) * dtype_bytes


def expert_token_flops(sizes, tokens: float) -> float:
    """``tokens`` tokens through one expert: the least a hit costs."""
    return 2.0 * expert_params(sizes) * tokens
