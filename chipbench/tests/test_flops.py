"""``chipbench/flops/`` against counts made by hand."""

import pytest

from chipbench import harness as H


def test_resnet50_forward_macs():
    f = H.load_module("flops", "resnet50")
    sizes = {"image_size": 224, "num_classes": 1000}
    # He et al. 2015, table 1: 3.8e9 multiply-adds for the 50-layer net
    # (the v1 block, stride on the first 1x1; the later v1.5 block that
    # strides its 3x3 costs 4.1e9)
    assert f.forward_macs(sizes) == pytest.approx(3.86e9, rel=0.005)
    # by hand: stem 7*7*3*64 at 112^2; classifier 2048*1000
    assert f.conv_shapes(sizes)[0] == (7, 3, 64, 112)
    stem = 7 * 7 * 3 * 64 * 112 * 112
    block1 = (64 * 256 + 64 * 64 + 9 * 64 * 64 + 64 * 256) * 56 * 56
    rest = f.forward_macs(sizes) - stem - 2048 * 1000
    assert rest > block1 and len(f.conv_shapes(sizes)) == 53
    assert f.train_flops_per_item(sizes) == 6 * f.forward_macs(sizes)


def test_opt_serve_counts():
    f = H.load_module("flops", "opt-1.3b-serve")
    sizes = {"hidden_size": 2048, "ffn_dim": 8192, "vocab_size": 50272,
             "num_hidden_layers": 24, "num_attention_heads": 32}
    d, layers = 2048, 24
    # 12 L d^2 matmul parameters (4 d^2 attention + 8 d^2 feed-forward)
    # plus the output head
    assert f.matmul_params(sizes) == 12 * layers * d * d + d * 50272
    assert f.token_flops(sizes, 0) == 2 * f.matmul_params(sizes)
    # the causal attention term: a token at context c does 4 L d c
    assert f.token_flops(sizes, 100) - f.token_flops(sizes, 0) == \
        4 * layers * d * 100
    # a prompt of 2: both tokens through the layers, one through the head
    assert f.prefill_flops(sizes, 2) == pytest.approx(
        f.token_flops(sizes, 1) + f.token_flops(sizes, 2)
        - 2 * d * 50272)
    # decode bytes = live K/V of the active rows: 2 (K,V) * L * d * 4 B
    assert f.kv_bytes_per_token(sizes) == 2 * layers * d * 4
