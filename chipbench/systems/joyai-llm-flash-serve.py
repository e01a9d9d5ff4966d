"""The system under test for ``joyai-llm-flash-serve``: the same
``DecoderModel`` + ``InferenceServer`` + ``PagePool`` as every served
configuration, built from the configuration's sizes with a layer plan
of latent attention, fed the benchmark's weights under the program's
leaf names."""

from __future__ import annotations

from paddle_tpu.serving.model import DecoderConfig

# a program whose decoder knows no latent attention cannot build this
# configuration: say so before the weights are drawn
if "kv_rank" not in DecoderConfig._fields:
    raise ImportError("paddle_tpu.serving.model.DecoderConfig knows no "
                      "latent attention: this program cannot run "
                      "joyai-llm-flash-serve")

LEAVES = {"attn_norm": "ln1", "ffn_norm": "ln2", "q_a_proj": "w_dq",
          "q_a_norm": "q_ln", "q_b_proj": "w_uq", "kv_a_proj": "w_dkv",
          "kv_a_norm": "kv_ln", "kv_b_proj": "w_ukv", "o_proj": "wo",
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
    attends through the latent with rotary positions on the shared key
    part; the leading layers have the dense feed-forward, the others
    the routed one with its shared expert."""
    assert sizes["scoring_func"] == "sigmoid" and sizes["norm_topk_prob"] \
        and sizes["topk_method"] == "noaux_tc" \
        and int(sizes["n_group"]) == int(sizes["topk_group"]) == 1 \
        and int(sizes["n_shared_experts"]) == 1 \
        and int(sizes["moe_layer_freq"]) == 1, \
        "the routed op is this family's: sigmoid scores, a bias that " \
        "chooses, one group, normalised, one shared expert"
    dense = int(sizes["first_k_dense_replace"])
    return tuple("latent+rope/" + ("swiglu" if i < dense
                                   else "routed+shared")
                 for i in range(int(sizes["num_hidden_layers"])))


def decoder_config(sizes) -> DecoderConfig:
    assert sizes["rope_scaling"] is None and not sizes["attention_bias"] \
        and not sizes["tie_word_embeddings"]
    return DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=int(sizes["hidden_size"]),
        heads=int(sizes["num_attention_heads"]),
        layers=int(sizes["num_hidden_layers"]),
        ffn=int(sizes["intermediate_size"]),
        max_context=int(sizes["max_model_len"]),
        plan=layer_plan(sizes),
        q_rank=int(sizes["q_lora_rank"]), kv_rank=int(sizes["kv_lora_rank"]),
        nope_dim=int(sizes["qk_nope_head_dim"]),
        rope_dim=int(sizes["qk_rope_head_dim"]),
        v_dim=int(sizes["v_head_dim"]),
        rope_interleave=bool(sizes["rope_interleave"]),
        experts=int(sizes["n_routed_experts"]),
        top_k=int(sizes["num_experts_per_tok"]),
        expert_ffn=int(sizes["moe_intermediate_size"]),
        route_scale=float(sizes["routed_scaling_factor"]),
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
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
