"""The readers' arithmetic on hand-made runs."""

import pytest

from chipbench import harness as H


class FakeMeter:
    def __init__(self, events):
        self.events = events

    seconds_between = H.CompileMeter.seconds_between
    compiles_between = H.CompileMeter.compiles_between


def request(prompt, n, t_submit, t_first, t_done, t_ready=None):
    return {"prompt": [2] * prompt, "tokens": [5] * n, "state": "done",
            "t_ready": t_submit if t_ready is None else t_ready,
            "t_submit": t_submit, "t_first": t_first, "t_done": t_done}


def test_rates_and_compile_counters():
    run = {"work": 900.0, "window_s": 45.0, "setup_s": 30.0,
           "ctx": {"t_process": 100.0}, "window": (131.0, 176.0),
           "meter": FakeMeter([
               (105.0, H.COMPILE_EVENTS[0], 2.0),
               (106.0, H.COMPILE_EVENTS[2], 5.0),
               (140.0, H.COMPILE_EVENTS[2], 1.0)])}
    assert H.load_module("readers", "host_rate").read(run) == 20.0
    assert H.load_module("readers", "compile_seconds").read(run) == 7.0
    assert H.load_module("readers", "compiles_in_window").read(run) == 1.0


def test_fallbacks_count_only_rows_with_a_reason():
    run = {"counters": [{"counter": "c", "path": "k", "reason": "",
                         "count": 16.0},
                        {"counter": "c", "path": "x", "reason": "off-tile",
                         "count": 3.0}]}
    assert H.load_module("readers", "fallback_dispatches").read(run) == 3.0


def test_request_clock_metrics():
    run = {"window": (0.0, 100.0), "requests": [
        request(128, 11, 0.0, 1.0, 3.0), request(256, 21, 3.1, 4.0, 9.0),
        request(128, 11, 99.0, 99.5, 120.0)]}
    # pooled: (2 + 5) s over (10 + 20) tokens; the third ends outside
    assert H.load_module("readers", "request_tpot_ms").read(run) == \
        pytest.approx(1e3 * 7.0 / 30.0)
    assert H.load_module("readers", "request_ttft_p50_ms").read(run) == \
        pytest.approx(900.0)


def test_tokens_between_spreads_a_request_evenly():
    tb = H.load_module("readers", "serve_mfu").tokens_between
    r = request(100, 11, 0.0, 10.0, 20.0)         # 10 decode tokens in 10 s
    k, ctx = tb(r, 12.0, 17.0)
    assert k == pytest.approx(5.0)
    # they are the 3rd..7th: context 100 + 1 + 4.5
    assert ctx == pytest.approx(105.5)
    assert tb(r, 30.0, 40.0) == (0.0, 0.0)
    still = dict(r, tokens=None, n_at_close=6, t_done=None, t_close=15.0)
    assert tb(still, 10.0, 15.0)[0] == pytest.approx(5.0)


def test_serve_mfu_counts_prompts_and_tokens():
    sizes = {"hidden_size": 2048, "ffn_dim": 8192, "vocab_size": 50272,
             "num_hidden_layers": 24, "num_attention_heads": 32}
    fl = H.load_module("flops", "opt-1.3b-serve")
    cell = type("C", (), {"config_name": "opt-1.3b-serve"})()
    r = request(128, 11, 0.0, 1.0, 11.0)
    run = {"cell": cell, "sizes": sizes, "window": (0.0, 20.0),
           "requests": [r],
           "ctx": {"here": H.HERE, "peaks": {"bf16_flops_per_s": 197e12}}}
    want = fl.prefill_flops(sizes, 128) + 10 * fl.token_flops(sizes, 134.0)
    got = H.load_module("readers", "serve_mfu").read(run)
    assert got == pytest.approx(100 * want / (20 * 197e12))
