"""Operations and least bytes of the served JoyAI-LLM-Flash decoder
from its shapes, as ``configs/joyai-llm-flash-serve.json`` runs it.

Matmul parameters a token really passes (the **active** ones): per layer
the latent attention's five matrices (W_dq d·r_q, W_uq r_q·H·(d_n+d_r),
W_dkv d·(r+d_r), W_ukv r·H·(d_n+d_v), W_o H·d_v·d); a dense layer's
SwiGLU (3 d f); a routed layer's router (d E), its
``num_experts_per_tok`` routed and ``n_shared_experts`` shared experts
(3 d f_e each); the output head d V.  Attention by the reference's
**expanded** form: a token whose context holds c positions multiplies
its query with the keys it sees (d_n + d_r lanes a head) and its weights
with their values (d_v lanes): 2·H·(d_n + d_r + d_v) FLOPs a visible
position and layer.  What a kernel over the latent rows does instead
(the absorbed form) is stated apart, per cached position."""

from __future__ import annotations


def _heads(sizes):
    return (int(sizes["num_attention_heads"]),
            int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"]),
            int(sizes["v_head_dim"]))


def attention_params(sizes) -> float:
    """The five matrices of one layer's latent attention."""
    d = int(sizes["hidden_size"])
    rq, r = int(sizes["q_lora_rank"]), int(sizes["kv_lora_rank"])
    h, dn, dr, dv = _heads(sizes)
    return float(d * rq + rq * h * (dn + dr) + d * (r + dr)
                 + r * h * (dn + dv) + h * dv * d)


def expert_params(sizes) -> float:
    """One expert: gate, up and down."""
    return 3.0 * int(sizes["hidden_size"]) \
        * int(sizes["moe_intermediate_size"])


def layer_matmul_params(sizes) -> float:
    """Active matmul parameters of all layers (no head)."""
    d = int(sizes["hidden_size"])
    layers = int(sizes["num_hidden_layers"])
    dense = int(sizes["first_k_dense_replace"])
    routed = d * int(sizes["n_routed_experts"]) + expert_params(sizes) * (
        int(sizes["num_experts_per_tok"]) + int(sizes["n_shared_experts"]))
    return layers * attention_params(sizes) + dense * 3.0 * d * int(
        sizes["intermediate_size"]) + (layers - dense) * routed


def head_params(sizes) -> float:
    return float(int(sizes["hidden_size"]) * int(sizes["vocab_size"]))


def prefill_pair_flops(sizes, pairs: float) -> float:
    """Expanded attention over ``pairs`` visible (query, key) pairs of
    one layer: scores over d_n + d_r lanes and values over d_v, every
    head (the unit of the prefill span's ``attn_pairs``)."""
    h, dn, dr, dv = _heads(sizes)
    return 2.0 * h * (dn + dr + dv) * pairs


def prefill_pair_bytes(sizes) -> float:
    """The packed kernel is bound by its products, not by its operands:
    no byte floor a pair."""
    return 0.0


def attention_flops(sizes, context: float) -> float:
    """Attention FLOPs of one token whose context (itself included)
    holds ``context`` positions, over all layers."""
    return int(sizes["num_hidden_layers"]) * prefill_pair_flops(sizes,
                                                                context)


def token_flops(sizes, context: float) -> float:
    """FLOPs of one token whose attention spans ``context`` positions."""
    return 2.0 * (layer_matmul_params(sizes) + head_params(sizes)) \
        + attention_flops(sizes, context)


def prefill_flops(sizes, prompt: int) -> float:
    """All prompt tokens through the layers, the output head on the
    last one only (it alone gives a token).  Token i sees i + 1
    positions in every layer."""
    return 2.0 * layer_matmul_params(sizes) * prompt \
        + 2.0 * head_params(sizes) \
        + attention_flops(sizes, prompt * (prompt + 1) / 2.0)


# ------------------------------------------------ per-unit work of a span
def latent_bytes_per_token(sizes, dtype_bytes: int = 2) -> float:
    """The cache row of one position in ONE layer, kv_lora_rank +
    qk_rope_head_dim numbers (the unit of the decode span's
    ``attended_tokens``): what any kernel over the latent cache must
    read of a position, once."""
    return float(int(sizes["kv_lora_rank"])
                 + int(sizes["qk_rope_head_dim"])) * dtype_bytes


def latent_attention_flops(sizes, positions: float) -> float:
    """Decode attention over ``positions`` cached rows of one layer in
    the absorbed form: every head's score over the row's r + d_r
    numbers and its output over the first r."""
    h, _, dr, _ = _heads(sizes)
    r = int(sizes["kv_lora_rank"])
    return 2.0 * h * ((r + dr) + r) * positions


def expert_bytes(sizes, dtype_bytes: int = 2) -> float:
    """The weights of one expert (the unit of the decode span's
    ``experts_hit``: what a step must read of an expert it hits)."""
    return expert_params(sizes) * dtype_bytes


def expert_token_flops(sizes, tokens: float) -> float:
    """``tokens`` tokens through one expert: the least a hit costs."""
    return 2.0 * expert_params(sizes) * tokens
