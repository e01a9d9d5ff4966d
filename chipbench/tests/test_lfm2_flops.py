"""``chipbench/flops/lfm2-24b-a2b-serve.py`` against counts made by hand
from the configuration's published widths, one layer of each kind."""

import pytest

from chipbench import harness as H

SIZES = H.load_json(H.named_file("configs", "lfm2-24b-a2b-serve",
                                 ".json"))["sizes"]
F = "lfm2-24b-a2b-serve"


def test_active_parameters_by_hand_one_layer_of_each_kind():
    f = H.load_module("flops", F)
    d = 2048
    # a conv layer's mixer: in_proj d x 3d and out_proj d x d (its 3
    # taps a lane, 6144 numbers, are no matrix)
    assert f.conv_mixer_params(SIZES) == d * 3 * d + d * d == 16_777_216
    # an attention layer's mixer: 32 query and 8 K/V heads of 64
    wq = wo = d * 32 * 64
    wk = wv = d * 8 * 64
    assert (wq, wk) == (4_194_304, 1_048_576)
    assert f.attention_params(SIZES) == wq + wk + wv + wo == 10_485_760
    expert = 3 * d * 1536
    assert f.expert_params(SIZES) == expert == 9_437_184
    # a token passes 4 experts of 64 and no shared one
    routed = d * 64 + 4 * expert
    dense = 3 * d * 11776
    assert dense == 72_351_744
    assert f.layer_matmul_params(SIZES) == 7 * 16_777_216 \
        + 2 * 10_485_760 + dense + 8 * routed
    assert f.layer_matmul_params(SIZES) == pytest.approx(513.8e6, rel=1e-3)
    assert f.head_params(SIZES) == d * 65536
    # every expert counted would be nine times as much
    assert f.layer_matmul_params(SIZES) < 0.11 * (
        7 * 16_777_216 + 2 * 10_485_760 + dense
        + 8 * (d * 64 + 64 * expert))


def test_attention_is_counted_on_the_attention_layers_alone():
    f = H.load_module("flops", F)
    pair = 4 * 32 * 64                     # 4·H·D a visible position
    assert f.layer_attention_flops(SIZES, 1.0) == pair == 8192
    base = f.token_flops(SIZES, 0)
    assert base == 2 * (f.layer_matmul_params(SIZES) + f.head_params(SIZES))
    # two of nine layers see the context; seven see none of it
    assert f.token_flops(SIZES, 3000) - base == 2 * pair * 3000
    # a prompt of 2: both tokens through the layers, one through the head
    assert f.prefill_flops(SIZES, 2) == pytest.approx(
        f.token_flops(SIZES, 1) + f.token_flops(SIZES, 2)
        - 2 * f.head_params(SIZES))
    assert f.prefill_flops(SIZES, 4096) == pytest.approx(
        2 * f.layer_matmul_params(SIZES) * 4096 + 2 * f.head_params(SIZES)
        + 2 * pair * (4096 * 4097 // 2))
    # a token costs 1.34 GFLOP at a context of 2500, of which
    # attention is 41 MFLOP: the context hardly weighs
    assert f.token_flops(SIZES, 2500.0) == pytest.approx(1.337e9, rel=1e-3)
    assert f.attention_flops(SIZES, 2500.0) == pytest.approx(41e6, rel=1e-2)


def test_the_spans_units():
    f = H.load_module("flops", F)
    # one expert's weights in bfloat16: what a step reads of a hit
    assert f.expert_bytes(SIZES) == 18_874_368
    assert f.expert_token_flops(SIZES, 1.0) == 2 * 9_437_184
    # K and V of one position in one attention layer: 2 x 8 x 64 x 2 B
    assert f.layer_kv_bytes_per_token(SIZES) == 2048
    # the seven conv layers keep 2 rows of 2048 numbers a sequence
    assert f.state_bytes_per_sequence(SIZES) == 57_344
    # which is what 14 positions hold in the two attention layers' pools
    assert f.state_bytes_per_sequence(SIZES) \
        / (2 * f.layer_kv_bytes_per_token(SIZES)) == 14


def test_a_steps_share_of_the_peak_by_hand():
    """``serve_mfu.serve`` for one request that decodes 100 tokens in a
    second at contexts about 2500, and nothing else in the window."""
    f = H.load_module("flops", F)
    serve_mfu = H.load_module("readers", "serve_mfu")
    cell = H.Cell(H.manifest(), "lfm2_serve_closed_c12")
    peak = 197e12
    req = {"prompt": [2] * 2449, "tokens": [3] * 101, "t_first": 10.0,
           "t_done": 11.0}
    run = {"cell": cell, "sizes": SIZES, "window": (10.0, 11.0),
           "requests": [req],
           "ctx": {"here": H.HERE, "peaks": {"bf16_flops_per_s": peak}}}
    by_hand = 100 * f.token_flops(SIZES, 2500.0) \
        + f.prefill_flops(SIZES, 2449)
    assert serve_mfu.read(run) == pytest.approx(
        100.0 * by_hand / peak, rel=1e-6)
