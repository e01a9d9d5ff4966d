"""The system under test for ``sdar-30b-a3b-serve``: the same
``DecoderModel`` + ``InferenceServer`` + ``PagePool`` as every served
configuration, built from the configuration's sizes with a layer plan of
routed Qwen3-MoE blocks and generation by diffusion over blocks, fed the
benchmark's weights under the program's leaf names."""

from __future__ import annotations

import numpy as np

from paddle_tpu.serving.model import DecoderConfig

# a program whose decoder generates one token a row a step cannot build
# this configuration: say so before the weights are drawn
if "block_length" not in DecoderConfig._fields:
    raise ImportError("paddle_tpu.serving.model.DecoderConfig has no block "
                      "length: this program cannot run sdar-30b-a3b-serve")

LEAVES = {"attn_norm": "ln1", "ffn_norm": "ln2", "q_norm": "qn",
          "k_norm": "kn", "experts_gate": "e_gate", "experts_up": "e_up",
          "experts_down": "e_down"}


def decoder_config(sizes) -> DecoderConfig:
    """Every layer attends with q/k head norms and rotary positions and
    routes its feed-forward by softmax; blocks of ``block_length``."""
    assert sizes["norm_topk_prob"] and int(sizes["decoder_sparse_step"]) == 1 \
        and not sizes["mlp_only_layers"] and not sizes["attention_bias"] \
        and not sizes["tie_word_embeddings"], \
        "the layer is sdar_moe's: routed everywhere, normalised, no bias"
    layers = int(sizes["num_hidden_layers"])
    return DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=int(sizes["hidden_size"]),
        heads=int(sizes["num_attention_heads"]), layers=layers,
        ffn=int(sizes["intermediate_size"]),
        max_context=int(sizes["max_model_len"]),
        plan=("full+rope+qknorm/routed",) * layers,
        kv_heads=int(sizes["num_key_value_heads"]),
        head_dim=int(sizes["head_dim"]),
        experts=int(sizes["num_experts"]),
        top_k=int(sizes["num_experts_per_tok"]),
        expert_ffn=int(sizes["moe_intermediate_size"]),
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        pos_embed=False, storage="bfloat16", route_score="softmax",
        block_length=int(sizes["block_length"]),
        denoise_steps=int(sizes["denoising_steps"]),
        mask_id=int(sizes["mask_token_id"]))


def program_weights(sizes, weights):
    """The benchmark's weights under the program's names; the router's
    selection bias, which this model has not, is zeros."""
    top = {"tok_embed": "embed", "final_norm": "ln_f", "lm_head": "lm_head"}
    out = {}
    for k, v in weights.items():
        if k in top:
            out[top[k]] = v
            continue
        _, i, leaf = k.split(".")
        out[f"l{i}.{LEAVES.get(leaf, leaf)}"] = v
    for i in range(int(sizes["num_hidden_layers"])):
        out[f"l{i}.router_bias"] = np.zeros(int(sizes["num_experts"]),
                                            np.float32)
    return out


def build(sizes, mix, weights):
    from paddle_tpu.serving.model import DecoderModel
    from paddle_tpu.serving.server import InferenceServer

    model = DecoderModel(program_weights(sizes, weights),
                         decoder_config(sizes))
    server = InferenceServer(
        model, max_batch=int(mix["max_batch"]), n_pages=int(mix["n_pages"]),
        page_size=int(mix["page_size"]), continuous=True)
    return model, server
