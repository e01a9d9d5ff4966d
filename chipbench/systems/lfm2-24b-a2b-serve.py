"""The system under test for ``lfm2-24b-a2b-serve``: the same
``DecoderModel`` + ``InferenceServer`` + ``PagePool`` as every served
configuration, built from the configuration's sizes with a layer plan
that mixes gated short-convolution layers with full-attention ones, fed
the benchmark's weights under the program's leaf names."""

from __future__ import annotations

from paddle_tpu.serving import model as decoder
from paddle_tpu.serving.model import DecoderConfig

# a program whose layer plan knows no conv mixer cannot build this
# configuration: say so before the weights are drawn
if "conv" not in getattr(decoder, "KINDS", ()):
    raise ImportError("paddle_tpu.serving.model's layer plan knows no "
                      "conv mixer: this program cannot run "
                      "lfm2-24b-a2b-serve")

LEAVES = {"operator_norm": "ln1", "ffn_norm": "ln2", "q_norm": "qn",
          "k_norm": "kn", "experts_gate": "e_gate", "experts_up": "e_up",
          "experts_down": "e_down"}


def leaf_name(ref_name: str) -> str:
    top = {"tok_embed": "embed", "final_norm": "ln_f", "lm_head": "lm_head"}
    if ref_name in top:
        return top[ref_name]
    _, i, leaf = ref_name.split(".")
    return f"l{i}.{LEAVES.get(leaf, leaf)}"


def layer_plan(sizes):
    """The configuration's layers in the decoder's words: a conv layer
    is the gated short convolution alone, an attention layer is full
    with q/k head norms and rotary positions; the leading layers have
    the dense feed-forward, the others the routed one, which has no
    shared expert."""
    assert sizes["norm_topk_prob"] and sizes["use_expert_bias"] \
        and not sizes["conv_bias"], \
        "the routed op is this family's: sigmoid scores, a bias that " \
        "chooses, normalised; the conv has no bias"
    dense = int(sizes["num_dense_layers"])
    return tuple(
        {"conv": "conv", "full_attention": "full+rope+qknorm"}[kind]
        + "/" + ("swiglu" if i < dense else "routed")
        for i, kind in enumerate(sizes["layer_types"]))


def decoder_config(sizes) -> DecoderConfig:
    rope = sizes["rope_parameters"]
    assert rope["rope_type"] == "default"
    return DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=int(sizes["hidden_size"]),
        heads=int(sizes["num_attention_heads"]),
        layers=int(sizes["num_hidden_layers"]),
        ffn=int(sizes["intermediate_size"]),
        max_context=int(sizes["max_model_len"]),
        plan=layer_plan(sizes),
        kv_heads=int(sizes["num_key_value_heads"]),
        conv_taps=int(sizes["conv_L_cache"]),
        experts=int(sizes["num_experts"]),
        top_k=int(sizes["num_experts_per_tok"]),
        expert_ffn=int(sizes["moe_intermediate_size"]),
        route_scale=float(sizes["routed_scaling_factor"]),
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(sizes["norm_eps"]),
        pos_embed=False, storage="bfloat16")


def build(sizes, mix, weights):
    from paddle_tpu.serving.model import DecoderModel
    from paddle_tpu.serving.server import InferenceServer

    model = DecoderModel({leaf_name(k): v for k, v in weights.items()},
                         decoder_config(sizes))
    server = InferenceServer(
        model, max_batch=int(mix["max_batch"]), n_pages=int(mix["n_pages"]),
        page_size=int(mix["page_size"]), continuous=True)
    return model, server
