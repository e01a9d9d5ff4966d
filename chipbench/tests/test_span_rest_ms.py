"""``readers/span_rest_ms.py`` (``decode_xla_ms.serve``): device time
inside a program span that no kernel accounts for, on a hand-made trace
and on the small recorded one."""

import os

import pytest

from chipbench import harness as H
from chipbench import tracelib as T

CHIP = "/device:TPU:0"
DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
KERNEL = ('%moe_gmm.3 = f32[64,1536]{1,0} custom-call(s32[65]{0} %c), '
          'custom_call_target="tpu_custom_call"')
ARGS = H.load_json(H.named_file("metrics", "decode_xla_ms.serve",
                                ".json"))["args"]


def run_of(trace):
    return {"trace": {"trace": trace, "busy_s": T.busy_seconds(trace),
                      "window_s": trace.window_s, "spans": []}}


def steps_like():
    """Decode steps at 1, 3, 5 and 7 s of a 0-8 s window, each 1.6 s:
    a fusion of 0.3 s, a kernel of 0.8 s and a while loop of 0.4 s that
    holds a kernel of 0.1 s.  The step at 7 s is cut by the window's
    edge; a prefill span at 2.7 s holds a fusion of its own."""
    ops, host = [], [(0.0, 8.0, T.WINDOW_SPAN),
                     (2.7, 2.9, "serve_prefill")]
    for t in (1.0, 3.0, 5.0, 7.0):
        host.append((t, t + 1.6, "serve_decode_step"))
        ops += [(t, t + 0.3, "%fusion.1 = f32[16,2048]"),
                (t + 0.3, t + 1.1, KERNEL),
                (t + 1.1, t + 1.5, "%while.2 = (s32[], f32[16])"),
                (t + 1.2, t + 1.3, KERNEL)]
    ops.append((2.75, 2.85, "%fusion.9 = f32[4096,2048]"))
    return T.Trace({CHIP: ops}, host)


def test_time_inside_whole_steps_that_is_in_no_kernel():
    read = H.load_module("readers", "span_rest_ms").read
    # three whole steps; each is busy 1.5 s, 0.9 s of it in kernels
    assert read(run_of(steps_like()), **ARGS) == pytest.approx(600.0)
    # with nothing excepted it is the steps' busy time
    assert read(run_of(steps_like()), span="serve_decode_step",
                except_patterns=["no_such_op"]) == pytest.approx(1500.0)
    # the prefill's fusion lies in no decode span
    assert read(run_of(steps_like()), span="serve_prefill",
                except_patterns=ARGS["except_patterns"]) \
        == pytest.approx(100.0)


def test_no_span_or_no_trace_reads_nothing():
    read = H.load_module("readers", "span_rest_ms").read
    assert read({"trace": None}, **ARGS) is None
    assert read(run_of(steps_like()), span="serve_no_such_step",
                except_patterns=[]) is None
    empty = T.Trace({CHIP: []}, [(0.0, 8.0, T.WINDOW_SPAN),
                                 (1.0, 2.0, "serve_decode_step")])
    assert read(run_of(empty), **ARGS) is None


def test_on_the_recorded_trace_the_parts_add_up():
    """Three ``burst`` spans of matrix products and their operand
    copies: what is left beside the products plus the products is the
    bursts' busy time, and the products are most of it."""
    read = H.load_module("readers", "span_rest_ms").read
    run = run_of(T.load(DATA))
    busy = read(run, span="burst", except_patterns=["no_such_op"])
    rest = read(run, span="burst", except_patterns=["convolution"])
    # an op's name is its whole HLO line, operands and all: anchor it
    products = read(run, span="burst", except_patterns=["^%copy-"])
    assert 0 < rest < 0.3 * busy
    assert rest + products == pytest.approx(busy, rel=1e-6)
    # 59 products of about 0.09 ms in three bursts
    assert products == pytest.approx(59 * 0.091 / 3, rel=0.1)
