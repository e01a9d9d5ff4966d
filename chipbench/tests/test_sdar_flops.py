"""``chipbench/flops/sdar-30b-a3b-serve.py`` against counts made by hand
from the configuration's published widths."""

import pytest

from chipbench import harness as H

SIZES = H.load_json(H.named_file("configs", "sdar-30b-a3b-serve",
                                 ".json"))["sizes"]
F = "sdar-30b-a3b-serve"


def test_matmul_parameters_by_hand():
    f = H.load_module("flops", F)
    d = 2048
    # q and o of 32 heads of 128, k and v of 4: 18.87 M
    assert f.attention_params(SIZES) == 2 * d * 4096 + 2 * d * 512 \
        == 18_874_368
    assert f.expert_params(SIZES) == 3 * d * 768 == 4_718_592
    # a token passes the router (d x 128) and 8 of the 128 experts
    layer = 18_874_368 + d * 128 + 8 * 4_718_592
    assert f.layer_matmul_params(SIZES) == 6 * layer == 341_311_488
    assert f.head_params(SIZES) == d * 151936 == 311_164_928
    # what the chip holds: every expert, the embedding and the untied
    # head: 4,361 M = 8.72 GB in bfloat16
    held = 6 * (18_874_368 + d * 128 + 128 * 4_718_592) \
        + 2 * 311_164_928
    assert held == pytest.approx(4.361e9, rel=1e-3)


def test_a_token_costs_the_rows_of_its_blocks_passes():
    f = H.load_module("flops", F)
    # 2 denoising steps and a commit: 3 query rows an emitted token
    assert f.passes_per_token(SIZES) == 3
    row = 2 * (341_311_488 + 311_164_928)
    assert f.row_flops(SIZES, 0) == row
    pair = 4 * 4096
    assert f.row_flops(SIZES, 1000) - row == 6 * pair * 1000
    # each query of a block sees up to the block's end: 2 more on average
    assert f.token_flops(SIZES, 1000) == 3 * (row + 6 * pair * 1002)


def test_the_prefill_is_block_causal():
    f = H.load_module("flops", F)
    pair = 4 * 4096
    # 2046 ids: 511 whole blocks of 4 are prefilled, block j's 4 queries
    # seeing (j + 1) x 4 positions; the head on the last position only
    m = 511
    assert f.prefill_flops(SIZES, 2046) == pytest.approx(
        2 * 341_311_488 * 2044 + 2 * 311_164_928
        + 6 * pair * 16 * m * (m + 1) / 2)
    assert f.prefill_flops(SIZES, 2044) == f.prefill_flops(SIZES, 2046)


def test_the_spans_units():
    f = H.load_module("flops", F)
    # K and V of one position in one layer: 2 x 4 heads x 128 x 2 B
    assert f.layer_kv_bytes_per_token(SIZES) == 2048
    # the block mode: 4 queries of 32 heads of 128, 4 FLOPs a lane
    assert f.layer_block_attention_flops(SIZES, 1.0) == 4 * 4 * 4096
    # the kernel's intensity, 32 FLOPs a byte, lies far under the v5e's
    # ridge (197e12 / 819e9 = 240): bytes bound it
    assert f.layer_block_attention_flops(SIZES, 1.0) \
        / f.layer_kv_bytes_per_token(SIZES) == 32
    assert f.expert_bytes(SIZES) == 2 * 4_718_592
    assert f.expert_token_flops(SIZES, 3.0) == 6 * 4_718_592
