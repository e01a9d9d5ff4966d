"""Operations and least bytes of the served SDAR-30B-A3B-Chat decoder
from its shapes, as ``configs/sdar-30b-a3b-serve.json`` runs it.

Matmul parameters a query row really passes (the **active** ones): per
layer the attention's q and output projections (2 d H D) and k, v
(2 d G D), the router (d E) and its ``num_experts_per_tok`` experts
(3 d f_e each); the output head d V.  Attention: 4 H D FLOPs a visible
position and layer.

Generation is by diffusion over blocks of B positions: every block is
``denoising_steps`` passes that reveal its positions and one that
commits it, each pass B query rows through every layer and the head.
So an emitted token costs ``denoising_steps + 1`` query rows, at a
context of its block's end (:func:`token_flops`): what the server
computed for it, denoise and commit alike.  A prefill runs the prompt's
whole blocks under the block-causal mask, position i seeing up to the
end of its block."""

from __future__ import annotations


def _dims(sizes):
    d, dh = int(sizes["hidden_size"]), int(sizes["head_dim"])
    return (d, int(sizes["num_attention_heads"]) * dh,
            int(sizes["num_key_value_heads"]) * dh)


def _block(sizes) -> int:
    return int(sizes["block_length"])


def attention_params(sizes) -> float:
    """One layer's attention: q, k, v and the output."""
    d, hd, gd = _dims(sizes)
    return 2.0 * d * hd + 2.0 * d * gd


def expert_params(sizes) -> float:
    """One expert: gate, up and down."""
    return 3.0 * int(sizes["hidden_size"]) \
        * int(sizes["moe_intermediate_size"])


def layer_matmul_params(sizes) -> float:
    """Active matmul parameters of all layers (no head)."""
    routed = int(sizes["hidden_size"]) * int(sizes["num_experts"]) \
        + int(sizes["num_experts_per_tok"]) * expert_params(sizes)
    return int(sizes["num_hidden_layers"]) * (attention_params(sizes)
                                              + routed)


def head_params(sizes) -> float:
    return float(int(sizes["hidden_size"]) * int(sizes["vocab_size"]))


def passes_per_token(sizes) -> int:
    """Query rows computed per emitted token: a block's B tokens take
    ``denoising_steps`` + 1 passes of B rows."""
    return int(sizes["denoising_steps"]) + 1


def row_flops(sizes, context: float) -> float:
    """One query row through the layers and the head, attending
    ``context`` positions in every layer."""
    _, hd, _ = _dims(sizes)
    return 2.0 * (layer_matmul_params(sizes) + head_params(sizes)) \
        + 4.0 * hd * int(sizes["num_hidden_layers"]) * context


def token_flops(sizes, context: float) -> float:
    """FLOPs the server computes for one emitted token whose position
    is about ``context``: its share of its block's passes, each query
    seeing up to the block's end."""
    return passes_per_token(sizes) * row_flops(
        sizes, context + _block(sizes) / 2.0)


def prefill_flops(sizes, prompt: int) -> float:
    """The prompt's whole blocks through the layers, the head on the
    last position only.  Block j's B queries see (j + 1)·B positions."""
    _, hd, _ = _dims(sizes)
    b = _block(sizes)
    m = prompt // b
    return 2.0 * layer_matmul_params(sizes) * m * b \
        + 2.0 * head_params(sizes) \
        + 4.0 * hd * int(sizes["num_hidden_layers"]) \
        * b * b * m * (m + 1) / 2.0


# ------------------------------------------------ per-unit work of a span
def layer_kv_bytes_per_token(sizes, dtype_bytes: int = 2) -> float:
    """K and V of one cached position in ONE layer (the unit of the
    decode span's ``attended_tokens``)."""
    return 2.0 * _dims(sizes)[2] * dtype_bytes


def layer_block_attention_flops(sizes, positions: float) -> float:
    """The block mode of the paged kernel over ``positions`` attended
    positions of one layer: B queries, each 4 H D FLOPs a position."""
    return 4.0 * _block(sizes) * _dims(sizes)[1] * positions


def expert_bytes(sizes, dtype_bytes: int = 2) -> float:
    """The weights of one expert (the unit of the decode span's
    ``experts_hit``: what a step must read of an expert it hits)."""
    return expert_params(sizes) * dtype_bytes


def expert_token_flops(sizes, tokens: float) -> float:
    """``tokens`` tokens through one expert: the least a hit costs."""
    return 2.0 * expert_params(sizes) * tokens
