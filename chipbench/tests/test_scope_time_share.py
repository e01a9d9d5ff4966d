"""``readers/scope_time_share.py``: shares of the busy time by the
program's scopes on a hand-made trace and table; what a program that
keeps no table reads; a join that broke reads nothing."""

import pytest

from chipbench import harness as H
from chipbench import tracelib as T
from paddle_tpu.utils import profiler

CHIP = "/device:TPU:0"
KERNEL = ('%paged_decode.3 = f32[16,1,2048]{2,1,0} custom-call(s32[16]{0} '
          '%c), custom_call_target="tpu_custom_call"')
PATHS = {
    "%fusion.1 = f32[16,2048]": "jit(<lambda>)/L0/mixer/qkv/dot_general",
    "%fusion.2 = f32[393216,2048]": "jit(<lambda>)/L0/mixer/cache_write/"
                                    "scatter",
    KERNEL: "jit(<lambda>)/L0/mixer/attend/paged_decode/pallas_call",
    "%fusion.3 = f32[16,8192]": "jit(<lambda>)/L0/ffn/dense/dot_general",
    "%while.4 = (s32[], f32[16])": "jit(<lambda>)/head/while",
    "%fusion.5 = f32[16,50272]": "jit(<lambda>)/head/while/body/"
                                 "dot_general",
    "%copy.6 = f32[16]": "",
}


def run_of(window=10.0):
    """A stretch of 10 s, busy for 9: qkv 1, the row scatter 0.5, the
    kernel 3, the feed-forward 2, a ``while`` of 2 under ``head`` that
    holds a product of 1.5, and a copy of 0.5 that has no name."""
    ops, t = [], 0.0
    for line, seconds in zip(PATHS, (1.0, 0.5, 3.0, 2.0, 2.0, 1.5, 0.5)):
        start = 7.0 if line.startswith("%fusion.5") else t
        ops.append((start, start + seconds, line))
        t = t if line.startswith("%fusion.5") else t + seconds
    trace = T.Trace({CHIP: ops}, [(0.0, window, T.WINDOW_SPAN)])
    return {"trace": {"trace": trace, "busy_s": T.busy_seconds(trace),
                      "window_s": trace.window_s}}


@pytest.fixture
def window_ops(monkeypatch):
    table = {CHIP: {line: profiler.OpInfo(path, 1, 0, 0)
                    for line, path in PATHS.items()}}
    monkeypatch.setattr(profiler, "last_window_ops", lambda: table)
    return table


def read(name, run):
    spec = H.load_json(H.named_file("metrics", name, ".json"))
    assert spec["reader"] == "scope_time_share"
    return H.load_module("readers", spec["reader"]).read(
        run, **spec["args"])


def test_shares_of_the_busy_time_by_scope(window_ops):
    run = run_of()
    assert run["trace"]["busy_s"] == pytest.approx(9.0)
    # the while's own half second and the product inside it
    assert read("head_time_share.serve", run) == pytest.approx(100 * 2 / 9)
    assert read("cache_write_time_share.serve", run) == \
        pytest.approx(100 * 0.5 / 9)
    # the mixer less its row write, less its kernel
    assert read("mixer_xla_time_share.serve", run) == \
        pytest.approx(100 * 1 / 9)
    assert read("dense_ffn_time_share.serve", run) == \
        pytest.approx(100 * 2 / 9)
    assert read("moe_glue_time_share.serve", run) == 0.0
    # under no scope and no kernel: the copy
    assert read("unscoped_time_share.serve", run) == \
        pytest.approx(100 * 0.5 / 9)
    # with the kernel the shares tile the busy time
    kernels = H.load_module("readers", "op_time_share").read(
        run, patterns=["tpu_custom_call"])
    assert kernels + sum(read(m + "_time_share.serve", run) for m in (
        "head", "cache_write", "mixer_xla", "dense_ffn", "unscoped")) \
        == pytest.approx(100.0)
    # one pass over the events a run, kept with the run
    assert len(run["trace"]["scope_rows"]) == len(PATHS)


def test_the_layer_engines_scopes_inside_the_transformations(window_ops):
    window_ops[CHIP].update({
        "%fusion.1 = f32[16,2048]": profiler.OpInfo(
            "jit(step)/transpose(jvp(batch_norm))/res2a_bn/mul", 1, 0, 0),
        "%fusion.3 = f32[16,8192]": profiler.OpInfo(
            "jit(step)/jvp(batch_norm)/res2a_bn/reduce_sum", 1, 0, 0),
        KERNEL: profiler.OpInfo(
            "jit(step)/jvp(batch_norm)/res2a_bn/conv_bn_fwd/pallas_call",
            1, 0, 0),
        "%while.4 = (s32[], f32[16])": profiler.OpInfo(
            "jit(step)/optimizer/while", 1, 0, 0),
        "%fusion.5 = f32[16,50272]": profiler.OpInfo(
            "jit(step)/optimizer/while/body/mul", 1, 0, 0)})
    run = run_of()
    # forward and backward, the fused conv's kernel left to the kernels
    assert read("bn_time_share.train", run) == pytest.approx(100 * 3 / 9)
    assert read("optimizer_time_share.train", run) == \
        pytest.approx(100 * 2 / 9)
    # the scatter's path is no layer's, the copy has none
    assert read("unscoped_time_share.train", run) == \
        pytest.approx(100 * 1 / 9)


def test_a_program_that_keeps_no_table_names_nothing(monkeypatch):
    """The parent of the PR that brought the scopes: its profiler has
    no ``last_window_ops``.  Every share reads 0 and ``unscoped`` all
    that is no kernel: true of that program, and no failed run."""
    monkeypatch.delattr(profiler, "last_window_ops")
    run = run_of()
    assert read("head_time_share.serve", run) == 0.0
    assert read("mixer_xla_time_share.serve", run) == 0.0
    assert read("bn_time_share.train", run) == 0.0
    assert read("unscoped_time_share.serve", run) == \
        pytest.approx(100 * 6 / 9)
    assert read("unscoped_time_share.train", run) == \
        pytest.approx(100 * 6 / 9)
    # as before a window has closed
    monkeypatch.setattr(profiler, "last_window_ops", lambda: None,
                        raising=False)
    assert read("unscoped_time_share.serve", run_of()) == \
        pytest.approx(100 * 6 / 9)


def test_a_broken_join_reads_nothing_never_zero(window_ops):
    """The table names other HLO lines than the events carry (under
    half of the busy time finds its line): nothing to read, which fails
    the run by the metric's name."""
    for line in list(window_ops[CHIP])[:5]:
        window_ops[CHIP]["other " + line] = window_ops[CHIP].pop(line)
    assert read("head_time_share.serve", run_of()) is None
    assert read("unscoped_time_share.serve", run_of()) is None
    # no trace: nothing either
    assert read("head_time_share.serve", {"trace": None}) is None


def test_the_tool_gives_the_recorded_traces_table(capsys):
    """``tools/scope_times.py`` on the small recorded trace: the matrix
    product under its jit's name alone, the two copies XLA made under
    none; inside the window's span the same."""
    import os

    from chipbench.tools import scope_times

    path = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")
    for argv in ([path, "--lines", "2"],
                 [path, "--span", T.WINDOW_SPAN, "--lines", "2"]):
        scope_times.main(argv)
        out = capsys.readouterr().out.splitlines()
        assert out[0].startswith("0.0060 s counted over 1 chip")
        rows = {line.split()[-2] + " " + line.split()[-1]: line.split()
                for line in out[2:4]}
        assert rows["(no scope)"][1] == "88.73" \
            and rows["(no name)"][1] == "11.27"
        assert "convolution_multiply_fusion bf16[2048,2048]" in out[4] \
            and out[4].endswith("'jit(<lambda>)/dot_general'")
    scope_times.main([path, "--row", "(no name)"])
    assert capsys.readouterr().out.splitlines()[0].startswith("0.0007 s")
