"""Operations and least bytes of the served LFM2-24B-A2B decoder from
its shapes, as ``configs/lfm2-24b-a2b-serve.json`` runs it.

Matmul parameters a token really passes (the **active** ones): a conv
layer's two projections (d·3d in, d·d out; its K taps a lane are no
matrix); an attention layer's q and output projections (2 d H D) and k,
v (2 d G D); a dense layer's SwiGLU (3 d f); a routed layer's router
(d E) and its ``num_experts_per_tok`` experts (3 d f_e each; no shared
expert); the output head d V.  Attention: a token whose context holds c
positions multiplies its query with the keys it sees and its weights
with their values: 4 H D FLOPs a visible position and **attention**
layer.  A conv layer sees no pair, whatever the context."""

from __future__ import annotations


def _dims(sizes):
    d, h = int(sizes["hidden_size"]), int(sizes["num_attention_heads"])
    dh = d // h
    return d, h * dh, int(sizes["num_key_value_heads"]) * dh


def _kinds(sizes):
    """(conv layers, attention layers) of the layers that are run."""
    conv = sum(t == "conv" for t in sizes["layer_types"])
    return conv, int(sizes["num_hidden_layers"]) - conv


def conv_mixer_params(sizes) -> float:
    """One conv layer's mixer: ``in_proj`` and ``out_proj``."""
    d = int(sizes["hidden_size"])
    return 4.0 * d * d


def attention_params(sizes) -> float:
    """One attention layer's mixer: q, k, v and the output."""
    d, hd, gd = _dims(sizes)
    return 2.0 * d * hd + 2.0 * d * gd


def expert_params(sizes) -> float:
    """One expert: gate, up and down."""
    return 3.0 * int(sizes["hidden_size"]) \
        * int(sizes["moe_intermediate_size"])


def layer_matmul_params(sizes) -> float:
    """Active matmul parameters of all layers (no head)."""
    d = int(sizes["hidden_size"])
    conv, attn = _kinds(sizes)
    layers, dense = int(sizes["num_hidden_layers"]), \
        int(sizes["num_dense_layers"])
    routed = d * int(sizes["num_experts"]) \
        + expert_params(sizes) * int(sizes["num_experts_per_tok"])
    return conv * conv_mixer_params(sizes) + attn * attention_params(sizes) \
        + dense * 3.0 * d * int(sizes["intermediate_size"]) \
        + (layers - dense) * routed


def head_params(sizes) -> float:
    return float(int(sizes["hidden_size"]) * int(sizes["vocab_size"]))


def attention_flops(sizes, context: float) -> float:
    """Attention FLOPs of one token whose context (itself included)
    holds ``context`` positions, over the attention layers."""
    return _kinds(sizes)[1] * layer_attention_flops(sizes, context)


def token_flops(sizes, context: float) -> float:
    """FLOPs of one token whose attention spans ``context`` positions."""
    return 2.0 * (layer_matmul_params(sizes) + head_params(sizes)) \
        + attention_flops(sizes, context)


def prefill_flops(sizes, prompt: int) -> float:
    """All prompt tokens through the layers, the output head on the
    last one only (it alone gives a token).  Token i sees i + 1
    positions on an attention layer and none on a conv layer."""
    return 2.0 * layer_matmul_params(sizes) * prompt \
        + 2.0 * head_params(sizes) \
        + attention_flops(sizes, prompt * (prompt + 1) / 2.0)


# ------------------------------------------------ per-unit work of a span
def layer_kv_bytes_per_token(sizes, dtype_bytes: int = 2) -> float:
    """K and V of one cached position in ONE attention layer (the unit
    of the decode span's ``attended_tokens``)."""
    return 2.0 * _dims(sizes)[2] * dtype_bytes


def layer_attention_flops(sizes, positions: float) -> float:
    """Decode attention over ``positions`` attended positions of one
    attention layer."""
    return 4.0 * _dims(sizes)[1] * positions


def state_bytes_per_sequence(sizes, dtype_bytes: int = 2) -> float:
    """What the conv layers keep of one sequence, whatever its length
    (the decode span's ``state_rows`` counts sequences times layers:
    one layer's share is this over the conv layers)."""
    return float(_kinds(sizes)[0] * (int(sizes["conv_L_cache"]) - 1)
                 * int(sizes["hidden_size"]) * dtype_bytes)


def expert_bytes(sizes, dtype_bytes: int = 2) -> float:
    """The weights of one expert (the unit of the decode span's
    ``experts_hit``: what a step must read of an expert it hits)."""
    return expert_params(sizes) * dtype_bytes


def expert_token_flops(sizes, tokens: float) -> float:
    """``tokens`` tokens through one expert: the least a hit costs."""
    return 2.0 * expert_params(sizes) * tokens
