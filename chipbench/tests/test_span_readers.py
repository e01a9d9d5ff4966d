"""The readers of what PR 25 added to the program — kernels' names and
work counter, the serve step's host phases, the decode span's live
K/V — on hand-made traces and span lists; and the program's account of
the conv kernels' work against the benchmark's own count."""

import os

import pytest

from chipbench import harness as H
from chipbench import tracelib as T

CHIP = "/device:TPU:0"
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def run_of(trace, spans=(), **more):
    tr = {"trace": trace, "busy_s": T.busy_seconds(trace),
          "window_s": trace.window_s, "spans": list(spans)}
    return {"trace": tr, "ctx": {"peaks": PEAKS, "here": H.HERE}, **more}


# ------------------------------------------------------- idle_share_under
def serve_like():
    """A 10 s window, two steps.  Device busy 1-4 and 5-9; idle 0-1
    (before the loop has work), 4-5 and 9-10."""
    ops = {CHIP: [(1.0, 4.0, "%fusion.1 = f32[8]"),
                  (5.0, 9.0, "%fusion.2 = f32[8]")]}
    host = [(0.0, 10.0, T.WINDOW_SPAN),
            (0.5, 4.6, "serve_loop_iter"), (4.7, 9.9, "serve_loop_iter"),
            (0.6, 4.5, "serve_decode_step"), (4.8, 9.8, "serve_decode_step"),
            (0.6, 0.8, "serve_step_build"), (0.8, 1.0, "decode_dispatch"),
            (1.0, 4.3, "decode_fetch"), (4.3, 4.5, "serve_step_emit"),
            (4.8, 4.9, "serve_step_build"), (4.9, 5.0, "decode_dispatch"),
            (5.0, 9.5, "decode_fetch"), (9.5, 9.8, "serve_step_emit")]
    return T.Trace(ops, host)


def test_idle_is_split_under_nested_spans_and_the_parts_add_up():
    read = H.load_module("readers", "idle_share_under").read
    run = run_of(serve_like())
    fetch = read(run, spans=["decode_fetch", "prefill_fetch"])
    build = read(run, spans=["serve_step_build", "decode_dispatch",
                             "prefill_dispatch"])
    emit = read(run, spans=["serve_step_emit"])
    loop = read(run, spans=["serve_loop_iter"],
                except_spans=["serve_prefill", "serve_decode_step"])
    step = read(run, spans=["serve_decode_step"])
    # idle 4.0-4.3 and 9.0-9.5 under fetch; 0.6-1.0 and 4.8-5.0 under
    # build+dispatch; 4.3-4.5 and 9.5-9.8 under emit; 0.5-0.6,
    # 4.5-4.6, 4.7-4.8 and 9.8-9.9 under the loop alone
    assert fetch == pytest.approx(8.0)
    assert build == pytest.approx(6.0)
    assert emit == pytest.approx(5.0)
    assert loop == pytest.approx(4.0)
    assert step == pytest.approx(fetch + build + emit)
    # what no span of the loop covers: 0-0.5, 4.6-4.7, 9.9-10
    idle = H.load_module("readers", "device_idle_share").read(run)
    assert idle == pytest.approx(30.0)
    assert fetch + build + emit + loop == pytest.approx(idle - 7.0)


def test_idle_under_spans_the_trace_does_not_have_reads_nothing():
    read = H.load_module("readers", "idle_share_under").read
    assert read(run_of(serve_like()), spans=["prefill_fetch"]) is None
    assert read({"trace": None}, spans=["decode_fetch"]) is None


def test_interval_arithmetic():
    m = H.load_module("readers", "idle_share_under")
    assert m.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert m.intersect([(0, 3), (5, 8)], [(2, 6), (7, 9)]) == \
        [(2, 3), (5, 6), (7, 8)]
    assert m.subtract([(0, 1)], []) == [(0, 1)]


# ------------------------------------------------------ span_work_roofline
DECODE = ('%_lambda_.3 = f32[512,1,64]{2,1,0:T(1,128)S(1)} custom-call('
          's32[16]{0} %c), custom_call_target="tpu_custom_call"')
RENAMED = ('%paged_decode.3 = f32[512,1,64]{2,1,0} custom-call(s32[16]{0} '
           '%c), custom_call_target="tpu_custom_call"')
SIZES = {"num_hidden_layers": 2, "hidden_size": 4}
ARGS = dict(
    span="serve_decode_step", attr="live_tokens",
    flops_fn="decode_attention_flops", bytes_fn="kv_bytes_per_token",
    patterns=H.load_json(H.named_file(
        "metrics", "paged_decode_kv_roofline.serve", ".json"))["args"][
            "patterns"])
EPOCH = 1.7e9            # the ring's clock starts elsewhere than the trace's


def ring_span(start_s, dur_s, **args):
    return {"name": "serve_decode_step", "ts": (EPOCH + start_s) * 1e6,
            "dur": dur_s * 1e6, "args": args}


def decode_like(kernel=DECODE):
    """Steps at about 1, 3, 5, 7 and 9 s of a 0-10 s window, some 1.5 s
    each, the decode kernel 1 s of each.  The step at -1 s is in the ring only
    (it opened before the profiler), the one at 9 s is cut by the
    window's edge, and the host plane alone holds one at 11 s."""
    ops, host, ring = [], [(0.0, 10.0, T.WINDOW_SPAN)], []
    for i, t in enumerate((-1.0, 1.0, 3.1, 5.3, 7.2, 9.0, 11.4)):
        ops += [(t + 0.2, t + 1.2, kernel), (t + 1.2, t + 1.4, "%fusion.1")]
        took = 1.5 + 0.01 * i                    # no two steps alike
        if t > 0:
            # the annotation opens a few microseconds before the span
            host.append((t - 3e-6, t + took + 2e-6, "serve_decode_step"))
        if t < 11:
            ring.append(ring_span(t, took, batch=2,
                                  live_tokens=10 * (i + 1)))
    return T.Trace({CHIP: ops}, host), ring


def test_kernel_time_inside_whole_steps_against_their_own_live_tokens():
    read = H.load_module("readers", "span_work_roofline").read
    fl = H.load_module("flops", "opt-1.3b-serve")
    cell = type("C", (), {"config_name": "opt-1.3b-serve"})()
    trace, ring = decode_like()
    run = run_of(trace, ring, cell=cell, sizes=SIZES)
    # whole steps inside the window: those at 1, 3, 5 and 7 s, which
    # state 20 + 30 + 40 + 50 live tokens; 4 s of kernel
    units = 140.0
    least = max(fl.decode_attention_flops(SIZES, 1.0) * units / 100.0,
                fl.kv_bytes_per_token(SIZES) * units / 10.0)
    assert read(run, **ARGS) == pytest.approx(100.0 * least / 4.0)
    # the kernel under the name it will take reads the same
    trace, ring = decode_like(RENAMED)
    assert read(run_of(trace, ring, cell=cell, sizes=SIZES), **ARGS) == \
        pytest.approx(100.0 * least / 4.0)


def test_nothing_matched_reads_nothing():
    read = H.load_module("readers", "span_work_roofline").read
    cell = type("C", (), {"config_name": "opt-1.3b-serve"})()
    trace, ring = decode_like()
    # the parent's spans carry no live_tokens
    bare = [dict(e, args={"batch": 2}) for e in ring]
    assert read(run_of(trace, bare, cell=cell, sizes=SIZES), **ARGS) is None
    assert read(run_of(trace, [], cell=cell, sizes=SIZES), **ARGS) is None
    trace, ring = decode_like("%fusion.9 = f32[8]")
    assert read(run_of(trace, ring, cell=cell, sizes=SIZES), **ARGS) is None
    assert read({"trace": None}, **ARGS) is None


def test_spans_are_paired_across_the_two_clocks():
    pair = H.load_module("readers", "span_work_roofline").pair
    trace, ring = decode_like()
    host = sorted(e for e in trace.host_spans
                  if e[2] == "serve_decode_step")
    got = pair(host, ring)
    assert [(round(h[0]), r["args"]["live_tokens"]) for h, r in got] == \
        [(1, 20), (3, 30), (5, 40), (7, 50), (9, 60)]
    # clocks that no shift reconciles pair nothing
    skewed = [dict(e, ts=e["ts"] + 1e3 * i * i) for i, e in enumerate(ring)]
    assert pair(host, skewed) == []


# ---------------------------------------------------- kernel_work_roofline
def test_named_kernels_against_the_programs_own_work_counter():
    from paddle_tpu import observe
    from paddle_tpu.ops import kernels as K

    class A:                                   # 40 bytes
        shape, dtype = (10,), type("D", (), {"itemsize": 4})()

    observe.REGISTRY.reset()
    # two traced calls of one kernel: 300 FLOPs and 80 bytes in all;
    # one of another that the trace does not hold
    K.record_kernel_work("t_fwd", 100.0, [A], [])
    K.record_kernel_work("t_fwd", 200.0, [A], [])
    K.record_kernel_work("t_bwd", 999.0, [A], [])
    ops = {CHIP: [(0.0, 2.0, "%jvp_t_fwd_.1 = f32[8] custom-call("),
                  (2.0, 5.0, "%jvp_t_fwd_.2 = f32[8] custom-call("),
                  (5.0, 9.0, "%t_fwd_bwd.1 = f32[8] custom-call(")]}
    run = run_of(T.Trace(ops, [(0.0, 10.0, T.WINDOW_SPAN)]))
    read = H.load_module("readers", "kernel_work_roofline").read
    kernels = {k: K.instruction_pattern(k) for k in ("t_fwd", "t_bwd")}
    # a call: 150 FLOPs / 100 = 1.5 s against 40 bytes / 10 = 4 s, so
    # the bytes bind; two events took 5 s
    assert read(run, kernels=kernels) == pytest.approx(100 * 2 * 4.0 / 5.0)
    assert read(run, kernels={"t_bwd": kernels["t_bwd"]}) is None
    observe.REGISTRY.reset()
    assert read(run, kernels=kernels) is None     # a program without it


def test_conv_work_counter_agrees_with_the_benchmarks_own_count():
    """One rehearsal-size ResNet-50 step, traced and not run: what the
    program's counter says the conv+BN kernels compute is the 3x3
    convolutions' share of ``flops/resnet50.py``'s count, forward and
    backward-data (the filter gradient runs under XLA)."""
    import numpy as np

    from paddle_tpu import observe
    from paddle_tpu.observe import costmodel

    cell = H.Cell(H.manifest(), "resnet50_train_b128")
    cfg = cell.config
    sizes, batch = cfg["sizes"], int(cfg["rehearsal"]["batch"])
    fl = H.load_module("flops", "resnet50")
    observe.REGISTRY.reset()
    trainer, _ = H.load_module("systems", "resnet50").build(
        sizes, cfg["optimizer"], 1)
    feed = {"image": np.zeros((batch, 3 * int(sizes["image_size"]) ** 2),
                              np.float32),
            "label": np.zeros((batch,), np.int32)}
    step = trainer._build_train_step()
    step.lower(*costmodel._step_args(trainer, feed))      # trace only
    work = H.load_module("readers", "kernel_work_roofline").work_per_call()
    rows = {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
            for s in observe.REGISTRY.find(
                "pallas_kernel_work_total").samples()}
    three = [(cin, cout, side) for k, cin, cout, side
             in fl.conv_shapes(sizes) if k == 3]
    one_way = sum(2.0 * batch * side * side * 9 * cin * cout
                  for cin, cout, side in three)
    forward = sum(v for (k, kind), v in rows.items()
                  if kind == "flops" and k == "conv_bn_fwd")
    backward = sum(v for (k, kind), v in rows.items()
                   if kind == "flops" and k != "conv_bn_fwd")
    assert forward == one_way and backward == one_way
    assert sum(v for (_, kind), v in rows.items() if kind == "calls") \
        == 2 * len(three) == 32
    assert set(work) <= {"conv_bn_fwd", "conv_bn_dx", "conv_bn_fwd_bwd",
                         "conv_bn_chain_bwd"}
    # and of the whole step's model FLOPs (three products a conv, the
    # 3x3 ones two of them in kernels) they are the 3x3 share
    share = (forward + backward) / (batch * fl.train_flops_per_item(sizes))
    macs3 = sum(9 * cin * cout * side * side for cin, cout, side in three)
    assert share == pytest.approx(2 / 3 * macs3 / fl.forward_macs(sizes))


# ------------------------------------------------------------ the entries
def test_pending_entries_agree_with_their_files():
    """What ``pending/per_layer.json`` holds is ready to be appended to
    ``BENCHMARK.json``: each entry has its metric file and reader, moves
    an end-to-end metric its cells report, and clashes with no name."""
    man = H.manifest()
    pending = H.load_json(os.path.join(H.HERE, "pending", "per_layer.json"))
    assert len(pending) == 8
    taken = {m["name"] for m in man["end_to_end"] + man["per_layer"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    layers = {m["layer"] for m in man["per_layer"]}
    for m in pending:
        assert m["name"] not in taken
        taken.add(m["name"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers
        spec = H.load_json(H.named_file("metrics", m["name"], ".json"))
        assert "workloads" not in spec
        for k, v in m.items():
            assert k == "workloads" or spec[k] == v
        assert hasattr(H.load_module("readers", spec["reader"]), "read")
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]]["workloads"]
