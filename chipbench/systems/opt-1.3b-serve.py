"""The system under test for ``opt-1.3b-serve``: ``DecoderModel`` +
``InferenceServer`` + ``PagePool`` at the configuration's sizes, fed the
benchmark's weights under the program's leaf names."""

from __future__ import annotations


def leaf_name(ref_name: str) -> str:
    top = {"tok_embed": "embed", "pos_embed": "pos_embed",
           "final_norm": "ln_f", "lm_head": "lm_head"}
    if ref_name in top:
        return top[ref_name]
    _, i, leaf = ref_name.split(".")
    leaf = {"attn_norm": "ln1", "ffn_norm": "ln2", "w_in": "w1",
            "w_out": "w2"}.get(leaf, leaf)
    return f"l{i}.{leaf}"


def build(sizes, mix, weights):
    from paddle_tpu.serving.model import DecoderConfig, DecoderModel
    from paddle_tpu.serving.server import InferenceServer

    cfg = DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=int(sizes["hidden_size"]),
        heads=int(sizes["num_attention_heads"]),
        layers=int(sizes["num_hidden_layers"]), ffn=int(sizes["ffn_dim"]),
        max_context=int(sizes["max_position_embeddings"]))
    model = DecoderModel({leaf_name(k): v for k, v in weights.items()}, cfg)
    server = InferenceServer(
        model, max_batch=int(mix["max_batch"]), n_pages=int(mix["n_pages"]),
        page_size=int(mix["page_size"]), continuous=True)
    return model, server
