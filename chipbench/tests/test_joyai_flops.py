"""``chipbench/flops/joyai-llm-flash-serve.py`` against counts made by
hand from the configuration's published widths."""

import pytest

from chipbench import harness as H

SIZES = H.load_json(H.named_file("configs", "joyai-llm-flash-serve",
                                 ".json"))["sizes"]
F = "joyai-llm-flash-serve"


def test_active_parameters_by_hand():
    f = H.load_module("flops", F)
    d, h = 2048, 32
    w_dq, w_uq = d * 1536, 1536 * h * (128 + 64)
    w_dkv, w_ukv, w_o = d * (512 + 64), 512 * h * (128 + 128), h * 128 * d
    assert (w_dq, w_uq, w_dkv, w_ukv, w_o) == (
        3_145_728, 9_437_184, 1_179_648, 4_194_304, 8_388_608)
    assert f.attention_params(SIZES) == 26_345_472          # 26.35 M
    expert = 3 * d * 768
    assert f.expert_params(SIZES) == expert == 4_718_592    # 4.72 M
    # a token passes 8 routed experts and the shared one, not 256
    routed = d * 256 + 9 * expert
    dense = 3 * d * 7168
    assert f.layer_matmul_params(SIZES) == 5 * 26_345_472 + dense \
        + 4 * routed
    assert f.layer_matmul_params(SIZES) == pytest.approx(347.7e6, rel=1e-3)
    assert f.head_params(SIZES) == d * 129280
    # every expert counted would be 13 times as much
    assert f.layer_matmul_params(SIZES) < 0.08 * (
        5 * 26_345_472 + dense + 4 * (d * 256 + 257 * expert))


def test_attention_by_the_expanded_form():
    f = H.load_module("flops", F)
    pair = 2 * 32 * (128 + 64 + 128)             # 2·H·320 a pair, a layer
    assert f.prefill_pair_flops(SIZES, 1.0) == pair == 20_480
    assert f.prefill_pair_bytes(SIZES) == 0.0
    base = f.token_flops(SIZES, 0)
    assert base == 2 * (f.layer_matmul_params(SIZES) + f.head_params(SIZES))
    # all five layers see the whole context
    assert f.token_flops(SIZES, 5000) - base == 5 * pair * 5000
    # a prompt of 2: both tokens through the layers, one through the head
    assert f.prefill_flops(SIZES, 2) == pytest.approx(
        f.token_flops(SIZES, 1) + f.token_flops(SIZES, 2)
        - 2 * f.head_params(SIZES))
    assert f.prefill_flops(SIZES, 7168) == pytest.approx(
        2 * f.layer_matmul_params(SIZES) * 7168 + 2 * f.head_params(SIZES)
        + 5 * pair * (7168 * 7169 // 2))
    # a token costs 1.74 GFLOP at a context of 5000
    assert f.token_flops(SIZES, 5000.0) == pytest.approx(1.737e9, rel=1e-3)


def test_the_spans_units():
    f = H.load_module("flops", F)
    # one expert's weights in bfloat16: what a step reads of a hit
    assert f.expert_bytes(SIZES) == 9_437_184
    assert f.expert_token_flops(SIZES, 1.0) == 2 * 4_718_592
    # one layer's cache row of one position: (512 + 64) numbers of 2 B
    assert f.latent_bytes_per_token(SIZES) == 1152
    # absorbed decode: every head's score over 576 and output over 512
    assert f.latent_attention_flops(SIZES, 1.0) == 2 * 32 * (576 + 512)
    # per-head K and V of the same position would be 32 x 320 numbers
    assert 32 * (192 + 128) * 2 / f.latent_bytes_per_token(SIZES) \
        == pytest.approx(17.8, rel=1e-2)


def test_a_steps_share_of_the_peak_by_hand():
    """``serve_mfu.serve`` for one request that decodes 100 tokens in a
    second at contexts about 5000, and nothing else in the window."""
    f = H.load_module("flops", F)
    serve_mfu = H.load_module("readers", "serve_mfu")
    cell = H.Cell(H.manifest(), "joyai_serve_closed_c12")
    peak = 197e12
    req = {"prompt": [2] * 4949, "tokens": [3] * 101, "t_first": 10.0,
           "t_done": 11.0}
    run = {"cell": cell, "sizes": SIZES, "window": (10.0, 11.0),
           "requests": [req],
           "ctx": {"here": H.HERE, "peaks": {"bf16_flops_per_s": peak}}}
    by_hand = 100 * f.token_flops(SIZES, 5000.0) \
        + f.prefill_flops(SIZES, 4949)
    assert serve_mfu.read(run) == pytest.approx(
        100.0 * by_hand / peak, rel=1e-6)
