"""``readers/span_attr_ratio.py`` on hand-made span lists: work done per
unit yielded, as ``row_passes_per_token.serve`` reads it from the block
launches' spans."""

import pytest

from chipbench import harness as H
from chipbench import tracelib as T


def run_of(spans):
    trace = T.Trace({}, [(0.0, 10.0, T.WINDOW_SPAN)])
    return {"trace": {"trace": trace, "spans": list(spans)}}


def test_work_per_unit_yielded_sums_over_the_spans_that_state_both():
    """Σ block_rows / Σ emitted over the block launches; a launch that
    emitted nothing still counts its rows, a span without the attributes
    (a one-token launch) counts for neither."""
    read = H.load_module("readers", "span_attr_ratio").read
    spans = [{"name": "serve_decode_step",
              "args": {"block_rows": 12, "emitted": 16}},
             {"name": "serve_decode_step",
              "args": {"block_rows": 12, "emitted": 0}},
             {"name": "serve_decode_step", "args": {"batch": 9}},
             {"name": "serve_prefill", "args": {"block_rows": 99}}]
    args = dict(span="serve_decode_step", num="block_rows", den="emitted")
    assert read(run_of(spans), **args) == pytest.approx(1.5)
    assert read(run_of(spans[2:]), **args) is None
    assert read(run_of(spans[1:2]), **args) is None
    assert read({"trace": None}, **args) is None
