"""The system under test for ``trinity-mini-serve``: the same
``DecoderModel`` + ``InferenceServer`` + ``PagePool`` as every served
configuration, built from the configuration's sizes with a layer plan,
fed the benchmark's weights under the program's leaf names."""

from __future__ import annotations

import math

from paddle_tpu.serving.model import DecoderConfig

# a program whose decoder has no layer plan cannot build this
# configuration: say so before the weights are drawn
if "plan" not in DecoderConfig._fields:
    raise ImportError("paddle_tpu.serving.model.DecoderConfig has no layer "
                      "plan: this program cannot run trinity-mini-serve")

LEAVES = {"attn_norm": "ln1", "attn_post_norm": "ln1p", "ffn_norm": "ln2",
          "ffn_post_norm": "ln2p", "q_norm": "qn", "k_norm": "kn",
          "experts_gate": "e_gate", "experts_up": "e_up",
          "experts_down": "e_down", "shared_gate": "s_gate",
          "shared_up": "s_up", "shared_down": "s_down"}


def leaf_name(ref_name: str) -> str:
    top = {"tok_embed": "embed", "final_norm": "ln_f", "lm_head": "lm_head"}
    if ref_name in top:
        return top[ref_name]
    _, i, leaf = ref_name.split(".")
    return f"l{i}.{LEAVES.get(leaf, leaf)}"


def layer_plan(sizes):
    """The configuration's layers in the decoder's words: every layer
    norms q and k, gates its output and norms what it adds; a sliding
    layer has the window and the rotary positions; the leading layers
    have the dense feed-forward, the others the routed one."""
    assert sizes["score_func"] == "sigmoid" and sizes["route_norm"] \
        and int(sizes["num_shared_experts"]) == 1, \
        "the routed op is afmoe's: sigmoid scores, normalised, one shared"
    plan = []
    for i, kind in enumerate(sizes["layer_types"]):
        attn = {"sliding_attention": "window+rope",
                "full_attention": "full"}[kind] + "+qknorm+gate+postnorm"
        ffn = "swiglu" if i < int(sizes["num_dense_layers"]) \
            else "routed+shared"
        plan.append(f"{attn}/{ffn}")
    return tuple(plan)


def decoder_config(sizes) -> DecoderConfig:
    d = int(sizes["hidden_size"])
    return DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=d,
        heads=int(sizes["num_attention_heads"]),
        layers=int(sizes["num_hidden_layers"]),
        ffn=int(sizes["intermediate_size"]),
        max_context=int(sizes["max_model_len"]),
        plan=layer_plan(sizes),
        kv_heads=int(sizes["num_key_value_heads"]),
        head_dim=int(sizes["head_dim"]),
        window=int(sizes["sliding_window"]),
        experts=int(sizes["num_experts"]),
        top_k=int(sizes["num_experts_per_tok"]),
        expert_ffn=int(sizes["moe_intermediate_size"]),
        route_scale=float(sizes["route_scale"]),
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        embed_scale=math.sqrt(d) if sizes["mup_enabled"] else 1.0,
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
