"""Operations and least bytes of the served AI21-Jamba2-3B decoder from
its shapes, as ``configs/jamba2-3b-serve.json`` runs it.

Matmul parameters a token really passes: a Mamba layer's four
projections (``in_proj`` d·2di, ``x_proj`` di·(R + 2N), ``dt_proj``
R·di, ``out_proj`` di·d; its K taps a channel, A, D and the norms are
no matrix); an attention layer's q and output projections (2 d H D) and
k, v (2 d G D); every layer's SwiGLU (3 d f); the output head d V (the
embedding, tied).  Attention: 4 H D FLOPs a visible position and
**attention** layer; a Mamba layer sees no pair, whatever the context.
The scan's elementwise work (7 FLOPs a channel and state number a
token, on the vector unit) is no model FLOP of the matrix unit and is
not counted here, as the conv layers' sums of other configurations are
not."""

from __future__ import annotations


def _dims(sizes):
    d, h = int(sizes["hidden_size"]), int(sizes["num_attention_heads"])
    return d, h * (d // h), int(sizes["num_key_value_heads"]) * (d // h)


def _kinds(sizes):
    """(Mamba layers, attention layers)."""
    period = int(sizes["attn_layer_period"])
    offset = int(sizes["attn_layer_offset"])
    layers = int(sizes["num_hidden_layers"])
    attn = sum(i % period == offset for i in range(layers))
    return layers - attn, attn


def _inner(sizes):
    return int(sizes["mamba_expand"]) * int(sizes["hidden_size"])


def mamba_mixer_params(sizes) -> float:
    """One Mamba layer's projections: in, x, dt and out."""
    d, di = int(sizes["hidden_size"]), _inner(sizes)
    r, n = int(sizes["mamba_dt_rank"]), int(sizes["mamba_d_state"])
    return float(d * 2 * di + di * (r + 2 * n) + r * di + di * d)


def attention_params(sizes) -> float:
    """One attention layer's mixer: q, k, v and the output."""
    d, hd, gd = _dims(sizes)
    return 2.0 * d * hd + 2.0 * d * gd


def layer_matmul_params(sizes) -> float:
    """Matmul parameters of all layers (no head)."""
    mamba, attn = _kinds(sizes)
    mlp = 3.0 * int(sizes["hidden_size"]) * int(sizes["intermediate_size"])
    return mamba * mamba_mixer_params(sizes) \
        + attn * attention_params(sizes) + (mamba + attn) * mlp


def head_params(sizes) -> float:
    return float(int(sizes["hidden_size"]) * int(sizes["vocab_size"]))


def layer_attention_flops(sizes, positions: float) -> float:
    """Attention over ``positions`` attended positions of one attention
    layer (the unit of the decode span's ``attended_tokens``)."""
    return 4.0 * _dims(sizes)[1] * positions


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
    last one only.  Token i sees i + 1 positions on an attention layer
    and none on a Mamba layer."""
    return 2.0 * layer_matmul_params(sizes) * prompt \
        + 2.0 * head_params(sizes) \
        + attention_flops(sizes, prompt * (prompt + 1) / 2.0)


# ------------------------------------------------ per-unit work of a span
def layer_kv_bytes_per_token(sizes, dtype_bytes: int = 2) -> float:
    """K and V of one cached position in ONE attention layer."""
    return 2.0 * _dims(sizes)[2] * dtype_bytes


def scan_token_flops(sizes, tokens: float) -> float:
    """The scan kernel's floor is bandwidth only: its exp and its
    multiply-adds run on the vector unit, whose peak ``peaks.json`` does
    not hold, so no FLOPs are set against the matrix unit's peak (the
    kernel may find its ceiling well under 100 % of this roofline)."""
    return 0.0


def scan_token_bytes(sizes, dtype_bytes: int = 2) -> float:
    """The least a scan of one prompt token in ONE Mamba layer moves
    (the unit of the prefill span's ``scan_tokens``): u, Δ and y once,
    B and C once, each at ``dtype_bytes`` a number.  The state in and
    out (16 × 5,120 × 4 B each way a sequence and layer) is left out: a
    unit is a token, and at 4,096 tokens a prompt it is 0.5 % of the
    rest, so the floor is lower and the share cannot pass 100 by it."""
    di, n = _inner(sizes), int(sizes["mamba_d_state"])
    return float((3 * di + 2 * n) * dtype_bytes)


def state_bytes_per_sequence(sizes) -> float:
    """What the Mamba layers keep of one sequence, whatever its length:
    the scan's state [N, di] in float32 and the newest K - 1 inputs of
    the convolution [di] in bfloat16, a layer."""
    di, n = _inner(sizes), int(sizes["mamba_d_state"])
    k = int(sizes["mamba_d_conv"])
    return float(_kinds(sizes)[0] * (n * di * 4 + (k - 1) * di * 2))
