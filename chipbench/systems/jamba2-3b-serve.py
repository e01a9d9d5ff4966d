"""The system under test for ``jamba2-3b-serve``: the same
``DecoderModel`` + ``InferenceServer`` + ``PagePool`` as every served
configuration, built from the configuration's sizes with a layer plan
of Mamba layers and two attention layers, fed the benchmark's weights
under the program's leaf names (``A_log`` and ``dt_bias`` as the
reference makes them from their draws: ``reference/jamba2-3b-serve.py::
layer_weights``)."""

from __future__ import annotations

from chipbench import harness as H
from paddle_tpu.serving import model as decoder
from paddle_tpu.serving.model import DecoderConfig

# a program whose layer plan knows no mamba mixer cannot build this
# configuration: say so before the weights are drawn
if "mamba" not in getattr(decoder, "KINDS", ()):
    raise ImportError("paddle_tpu.serving.model's layer plan knows no "
                      "mamba mixer: this program cannot run "
                      "jamba2-3b-serve")

CONFIG = "jamba2-3b-serve"
LEAVES = {"input_norm": "ln1", "pre_ff_norm": "ln2"}


def layer_plan(sizes):
    """The configuration's layers in the decoder's words: a Mamba layer
    is the mamba mixer alone, an attention layer is full with no rotary
    positions; every feed-forward is the dense SwiGLU."""
    ref = H.load_module("reference", CONFIG)
    return tuple(("full" if ref.attends(sizes, i) else "mamba") + "/swiglu"
                 for i in range(int(sizes["num_hidden_layers"])))


def decoder_config(sizes) -> DecoderConfig:
    assert sizes["tie_word_embeddings"] and int(sizes["num_experts"]) == 1 \
        and sizes["mamba_conv_bias"] and not sizes["mamba_proj_bias"]
    d = int(sizes["hidden_size"])
    return DecoderConfig(
        vocab=int(sizes["vocab_size"]), dim=d,
        heads=int(sizes["num_attention_heads"]),
        layers=int(sizes["num_hidden_layers"]),
        ffn=int(sizes["intermediate_size"]),
        max_context=int(sizes["max_model_len"]),
        plan=layer_plan(sizes),
        kv_heads=int(sizes["num_key_value_heads"]),
        conv_taps=int(sizes["mamba_d_conv"]),
        ssm_inner=int(sizes["mamba_expand"]) * d,
        ssm_state=int(sizes["mamba_d_state"]),
        dt_rank=int(sizes["mamba_dt_rank"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        pos_embed=False, tied_head=True, storage="bfloat16")


def program_weights(sizes, weights):
    """The benchmark's weights under the program's names."""
    ref = H.load_module("reference", CONFIG)
    out = {"embed": weights["tok_embed"], "ln_f": weights["final_norm"]}
    for i in range(int(sizes["num_hidden_layers"])):
        for k, v in ref.layer_weights(weights, sizes, i).items():
            out[f"l{i}.{LEAVES.get(k, k)}"] = v
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
