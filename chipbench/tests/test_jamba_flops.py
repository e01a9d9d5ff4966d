"""``chipbench/flops/jamba2-3b-serve.py`` against counts made by hand
from the configuration's published widths, one layer of each kind."""

import pytest

from chipbench import harness as H

SIZES = H.load_json(H.named_file("configs", "jamba2-3b-serve",
                                 ".json"))["sizes"]
F = "jamba2-3b-serve"


def test_matmul_parameters_by_hand_one_layer_of_each_kind():
    f = H.load_module("flops", F)
    d, di = 2560, 5120
    # a Mamba mixer's four projections; its 4 taps a channel and bias,
    # A_log, D, dt's bias and the three norms (123,952 numbers) are no
    # matrix: with them the mixer is the issue's 41.24 M
    mamba = d * 2 * di + di * (160 + 2 * 16) + 160 * di + di * d
    assert f.mamba_mixer_params(SIZES) == mamba == 41_123_840
    assert mamba + di * 4 + di + di * 16 + di + di + 160 + 16 + 16 \
        == 41_241_792
    # an attention mixer: 20 query heads and 1 K/V head of 128
    assert f.attention_params(SIZES) == 2 * d * d + 2 * d * 128 \
        == 13_762_560
    mlp = 3 * d * 8192
    assert f.layer_matmul_params(SIZES) == 26 * mamba \
        + 2 * 13_762_560 + 28 * mlp == 2_858_352_640
    # the tied head is the embedding: counted once as a product
    assert f.head_params(SIZES) == d * 65536 == 167_772_160


def test_attention_is_counted_on_the_attention_layers_alone():
    f = H.load_module("flops", F)
    pair = 4 * 20 * 128
    assert f.layer_attention_flops(SIZES, 1.0) == pair == 10_240
    base = f.token_flops(SIZES, 0)
    assert base == 2 * (2_858_352_640 + 167_772_160)
    # two of 28 layers see the context
    assert f.token_flops(SIZES, 9000) - base == 2 * pair * 9000
    assert f.prefill_flops(SIZES, 16384) == pytest.approx(
        2 * 2_858_352_640 * 16384 + 2 * 167_772_160
        + 2 * pair * (16384 * 16385 // 2))
    # ≈ 5.9 GFLOP a prompt token at 16,384: 5.72 through the layers,
    # 0.17 of attention (the issue reckoned 5.8)
    assert f.prefill_flops(SIZES, 16384) / 16384 == pytest.approx(
        5.88e9, rel=0.01)


def test_the_spans_units():
    f = H.load_module("flops", F)
    # K and V of one position in one attention layer: 2 x 128 x 2 B
    assert f.layer_kv_bytes_per_token(SIZES) == 512
    # a scan token of one Mamba layer: u, Δ and y at 2 B a channel, B
    # and C at 2 B a state number; no FLOPs against the matrix unit
    assert f.scan_token_bytes(SIZES) == (3 * 5120 + 2 * 16) * 2 == 30_784
    assert f.scan_token_flops(SIZES, 1.0) == 0.0
    # a sequence keeps 26 x (16 x 5120 x 4 B + 3 x 5120 x 2 B): the
    # issue's 9.32 MB, what 9,100 positions hold in the two attention
    # layers' pools
    assert f.state_bytes_per_sequence(SIZES) == 26 * (327_680 + 30_720) \
        == 9_318_400
    assert f.state_bytes_per_sequence(SIZES) \
        / (2 * f.layer_kv_bytes_per_token(SIZES)) == 9100
