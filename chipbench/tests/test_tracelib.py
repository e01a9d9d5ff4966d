"""The trace reduction on a recorded trace (three bursts of matrix
products with the host asleep in a named span between them, recorded on
a v5e by ``tools/record_small_trace.py``) and on hand-made events."""

import os

import pytest

from chipbench import harness as H
from chipbench import tracelib as T

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def hand_made():
    ops = {"/device:TPU:0": [(0.0, 1.0, "fusion.1"), (0.5, 1.5, "conv.2"),
                             (3.0, 4.0, "custom-call.7"),
                             (3.2, 3.4, "custom-call.8")]}
    spans = [(0.0, 5.0, T.WINDOW_SPAN), (1.4, 3.1, "feed"),
             (1.0, 4.5, "train_step")]
    return T.Trace(ops, spans)


def test_busy_is_a_union_not_a_sum():
    tr = hand_made()
    assert tr.window_s == 5.0
    assert T.busy_seconds(tr) == pytest.approx(2.5)


def test_kernel_time_by_pattern_takes_nested_events_once():
    secs, n = T.kernel_seconds(hand_made(), ["custom-call"])
    assert (secs, n) == (pytest.approx(1.0), 2)


def test_a_pattern_that_matches_nothing_reads_nothing():
    secs, n = T.kernel_seconds(hand_made(), ["no_such_kernel"])
    assert n == 0
    reader = H.load_module("readers", "op_time_share")
    run = {"trace": {"trace": hand_made(), "busy_s": 2.5, "window_s": 5.0}}
    assert reader.read(run, patterns=["no_such_kernel"]) is None
    assert reader.read(run, patterns=["custom-call"]) == pytest.approx(40.0)


def test_gaps_are_named_by_the_innermost_covering_span():
    gaps = T.idle_gaps(hand_made(), 3)
    assert gaps[0] == ["feed", pytest.approx(1.5)]
    assert gaps[1] == ["train_step", pytest.approx(1.0)] or \
        gaps[1][0] == "no_span"


def test_top_ops_merge_numbered_instances():
    rows = dict((k, v) for k, v in T.top_ops(hand_made(), 5))
    assert rows["custom-call"] == pytest.approx(1.2)
    line = ('%_lambda_.3 = f32[512,1,64]{2,1,0:T(1,128)S(1)} custom-call('
            's32[16]{0} %c), custom_call_target="tpu_custom_call"')
    assert T.short_name(line) == "_lambda_ f32[512,1,64] tpu_custom_call"


@pytest.mark.skipif(not os.path.isfile(DATA), reason="no recorded trace")
def test_recorded_trace():
    tr = T.load(DATA)
    assert tr.device_ops, "no device plane with an 'XLA Ops' line"
    busy = T.busy_seconds(tr)
    assert 0 < busy < tr.window_s
    # three sleeps of 50 ms lie inside the window
    assert tr.window_s - busy >= 0.14
    gaps = T.idle_gaps(tr, 3, names=("burst", "idle_sleep"))
    assert [g[0] for g in gaps] == ["idle_sleep"] * 3
    assert all(g[1] >= 0.045 for g in gaps)
    secs, n = T.kernel_seconds(tr, ["fusion", "dot", "convolution"])
    assert n >= 50 and secs == pytest.approx(busy, rel=0.2)
    assert T.kernel_seconds(tr, ["no_such_kernel"])[1] == 0
