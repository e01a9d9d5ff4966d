"""``chipbench/flops/trinity-mini-serve.py`` against counts made by
hand from the configuration's published widths."""

import pytest

from chipbench import harness as H

SIZES = H.load_json(H.named_file("configs", "trinity-mini-serve",
                                 ".json"))["sizes"]


def test_active_parameters_by_hand():
    f = H.load_module("flops", "trinity-mini-serve")
    d, hd, gd = 2048, 32 * 128, 4 * 128
    attn = 3 * d * hd + 2 * d * gd                 # q, gate, o; k, v
    assert attn == 27_262_976
    expert = 3 * d * 1024
    assert f.expert_params(SIZES) == expert == 6_291_456
    # a token passes 8 routed experts and the shared one, not 128
    routed = d * 128 + 9 * expert
    dense = 3 * d * 6144
    assert f.layer_matmul_params(SIZES) == 5 * attn + dense + 4 * routed
    assert f.layer_matmul_params(SIZES) == pytest.approx(401.6e6, rel=1e-3)
    assert f.head_params(SIZES) == d * 200192
    # every expert counted would be 5 * attn + dense + 4 * (129 experts)
    assert f.layer_matmul_params(SIZES) < 0.13 * (
        5 * attn + dense + 4 * (d * 128 + 129 * expert))


def test_attention_sees_the_window_on_sliding_layers():
    f = H.load_module("flops", "trinity-mini-serve")
    hd = 32 * 128
    base = f.token_flops(SIZES, 0)
    assert base == 2 * (f.layer_matmul_params(SIZES) + f.head_params(SIZES))
    # inside the window all five layers see the whole context
    assert f.token_flops(SIZES, 1000) - base == 4 * hd * 5 * 1000
    # past it the four sliding layers see 2048, the full layer all
    assert f.token_flops(SIZES, 6000) - base == 4 * hd * (4 * 2048 + 6000)
    # a prompt of 2: both tokens through the layers, one through the head
    assert f.prefill_flops(SIZES, 2) == pytest.approx(
        f.token_flops(SIZES, 1) + f.token_flops(SIZES, 2)
        - 2 * f.head_params(SIZES))
    # a prompt of 4096: positions seen on a sliding layer are the
    # triangle up to the window, then 2048 a token
    seen_sliding = 2048 * 2049 // 2 + 2048 * 2048
    seen_full = 4096 * 4097 // 2
    assert f.prefill_flops(SIZES, 4096) == pytest.approx(
        2 * f.layer_matmul_params(SIZES) * 4096 + 2 * f.head_params(SIZES)
        + 4 * hd * (4 * seen_sliding + seen_full))


def test_a_steps_share_of_the_peak_by_hand():
    """``serve_mfu.serve`` for one request that decodes 100 tokens in a
    second at contexts about 3000, and nothing else in the window."""
    f = H.load_module("flops", "trinity-mini-serve")
    serve_mfu = H.load_module("readers", "serve_mfu")
    cell = H.Cell(H.manifest(), "trinity_serve_closed_c12")
    peak = 197e12
    req = {"prompt": [2] * 2949, "tokens": [3] * 101, "t_first": 10.0,
           "t_done": 11.0}
    run = {"cell": cell, "sizes": SIZES, "window": (10.0, 11.0),
           "requests": [req],
           "ctx": {"here": H.HERE, "peaks": {"bf16_flops_per_s": peak}}}
    # 100 tokens at a mean context of 2950 + 50 = 3000, and the prompt's
    # prefill (its first token fell in the window)
    by_hand = 100 * f.token_flops(SIZES, 3000.0) \
        + f.prefill_flops(SIZES, 2949)
    assert serve_mfu.read(run) == pytest.approx(
        100.0 * by_hand / peak, rel=1e-6)
    # a token costs 1.81 GFLOP at that context: 0.09 % of the peak a
    # hundred a second
    assert f.token_flops(SIZES, 3000.0) == pytest.approx(1.806e9, rel=2e-3)


def test_the_spans_units():
    f = H.load_module("flops", "trinity-mini-serve")
    # one expert's weights in bfloat16: what a step reads of a hit
    assert f.expert_bytes(SIZES) == 12_582_912
    assert f.expert_token_flops(SIZES, 1.0) == 2 * 6_291_456
    # one layer's K and V of one position: 2 * 4 heads * 128 * 2 B
    assert f.layer_kv_bytes_per_token(SIZES) == 2048
    assert f.layer_attention_flops(SIZES, 1.0) == 4 * 32 * 128
