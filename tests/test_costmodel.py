"""Performance observatory, attribution + memory legs (ISSUE 10).

Two layers:

1. **Pure parser/roofline** (no backend): synthetic optimized-HLO text
   exercises region attribution through ``op_name`` metadata (autodiff
   ``transpose(jvp(...))`` unwrapping), the dot/convolution FLOP
   formulas, while-loop trip amortization, kernel-level HBM byte
   charging, and the roofline verdict pins the ISSUE names — one known
   memory-bound (elementwise) and one compute-bound (matmul) region
   against fixed synthetic peaks.
2. **Real compiled step** (CPU harness): ``analyze_trainer_step`` on a
   tiny model — per-region FLOPs sum to the whole-step total within
   tolerance, layer names from the ``jax.named_scope`` threading appear
   as regions with nonzero backward share, and the memory accounting
   (``observe/memory.py``) attributes >= 90% of ``hbm_in_use_bytes`` to
   the trainer's known pytrees after a step.
"""

import math

import numpy as np
import pytest

from paddle_tpu.observe import REGISTRY, costmodel
from paddle_tpu.observe import memory as omem


# ---------------------------------------------------------- synthetic HLO
# A hand-written "optimized module": one matmul region (dot 256x512 @
# 512x256), one elementwise region (add over 4 MB of f32), an autodiff
# transpose wrapper, and a while loop with a recoverable trip count.
SYNTH_HLO = """\
HloModule jit_step, entry_computation_layout={(f32[256,512]{1,0}, f32[512,256]{1,0}, /*index=2*/f32[1048576]{0})->f32[256,256]{1,0}}

%cond.1 (p.0: (s32[], f32[1048576])) -> pred[] {
  %p.0 = (s32[], f32[1048576]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element((s32[], f32[1048576]{0}) %p.0), index=0
  %bound.0 = s32[] constant(10)
  ROOT %lt.0 = pred[] compare(s32[] %gte.0, s32[] %bound.0), direction=LT
}

%body.1 (p.1: (s32[], f32[1048576])) -> (s32[], f32[1048576]) {
  %p.1 = (s32[], f32[1048576]{0}) parameter(0)
  %gte.1 = s32[] get-tuple-element((s32[], f32[1048576]{0}) %p.1), index=0
  %one.0 = s32[] constant(1)
  %next.0 = s32[] add(s32[] %gte.1, s32[] %one.0)
  %gte.2 = f32[1048576]{0} get-tuple-element((s32[], f32[1048576]{0}) %p.1), index=1
  %ew.0 = f32[1048576]{0} add(f32[1048576]{0} %gte.2, f32[1048576]{0} %gte.2), metadata={op_name="jit(step)/jit(main)/jvp(__ew_1__)/add"}
  ROOT %tup.0 = (s32[], f32[1048576]{0}) tuple(s32[] %next.0, f32[1048576]{0} %ew.0)
}

ENTRY %main.1 (Arg_0.1: f32[256,512], Arg_1.2: f32[512,256], Arg_2.3: f32[1048576]) -> f32[256,256] {
  %Arg_0.1 = f32[256,512]{1,0} parameter(0)
  %Arg_1.2 = f32[512,256]{1,0} parameter(1)
  %Arg_2.3 = f32[1048576]{0} parameter(2)
  %zero.1 = s32[] constant(0)
  %init.0 = (s32[], f32[1048576]{0}) tuple(s32[] %zero.1, f32[1048576]{0} %Arg_2.3)
  %loop.0 = (s32[], f32[1048576]{0}) while((s32[], f32[1048576]{0}) %init.0), condition=%cond.1, body=%body.1
  %mm.0 = f32[256,256]{1,0} dot(f32[256,512]{1,0} %Arg_0.1, f32[512,256]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jit(main)/jvp(__mm_1__)/dot_general"}
  %gmm.0 = f32[512,256]{1,0} dot(f32[256,512]{1,0} %Arg_0.1, f32[256,256]{1,0} %mm.0), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jit(main)/transpose(jvp(__mm_1__))/dot_general"}
  ROOT %out.0 = f32[256,256]{1,0} add(f32[256,256]{1,0} %mm.0, f32[256,256]{1,0} %mm.0), metadata={op_name="jit(step)/jit(main)/jvp(__mm_1__)/add"}
}
"""

#: Synthetic peaks with a ridge of 10 flop/B: the matmul region
#: (intensity ~39 — two dots over ~3.4 MB of operands) pins
#: compute-bound, the elementwise region (intensity 1/12) memory-bound.
PEAKS = {"flops": 1e12, "bw": 1e11, "ridge": 10.0, "source": "test"}


def test_parse_hlo_finds_entry_and_computations():
    comps = costmodel.parse_hlo(SYNTH_HLO)
    assert set(comps) == {"cond.1", "body.1", "main.1"}
    assert comps["main.1"].is_entry
    assert not comps["cond.1"].is_entry
    # the /*index=N*/ position comments XLA prints in long parameter
    # lists must not knock out the header match (the "=" inside them)
    assert len(comps["main.1"].instrs) == 9


def test_attribute_regions_flops_and_autodiff_unwrap():
    rep = costmodel.attribute(SYNTH_HLO, {"__mm_1__", "__ew_1__"})
    mm = rep["regions"]["__mm_1__"]
    ew = rep["regions"]["__ew_1__"]
    # fwd dot 2*256*512*256 + grad dot 2*512*256*256 + the output add
    assert mm["flops"] == pytest.approx(2 * 256 * 512 * 256 * 2
                                        + 256 * 256)
    # transpose(jvp(x)) unwraps to x and lands in the SAME region,
    # tagged backward
    assert mm["bwd_flops"] == pytest.approx(2 * 512 * 256 * 256)
    # loop body elementwise: counted once in the totals...
    assert ew["flops_once"] == pytest.approx(1048576)
    # ...and trip-amortized (x10) in the executed figures
    assert ew["flops"] == pytest.approx(10 * 1048576)
    assert rep["while_trips"] == {"loop.0": 10}
    # counter bookkeeping (s32 adds, tuples) stays out of known regions
    assert rep["regions"]["_unattributed"]["flops"] < 100


def test_attribute_charges_bytes_at_kernel_level():
    rep = costmodel.attribute(SYNTH_HLO, {"__mm_1__", "__ew_1__"})
    # the elementwise add touches 3 x 4 MB per trip, 10 trips; tuple /
    # get-tuple-element plumbing charges nothing
    assert rep["regions"]["__ew_1__"]["bytes"] == pytest.approx(
        10 * 3 * 1048576 * 4)
    mm_bytes = rep["regions"]["__mm_1__"]["bytes"]
    assert mm_bytes >= (256 * 512 + 512 * 256 + 256 * 256) * 4


#: A scan-body shape: the carry written through dynamic-update-slice
#: and the input read through dynamic-slice — XLA aliases/streams the
#: slices, so the whole buffers must NOT be charged per trip.
DUS_HLO = """\
%body.2 (p.1: (s32[], f32[100,1024], f32[100,1024])) -> (s32[], f32[100,1024], f32[100,1024]) {
  %p.1 = (s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) parameter(0)
  %i.0 = s32[] get-tuple-element((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %p.1), index=0
  %one.0 = s32[] constant(1)
  %next.0 = s32[] add(s32[] %i.0, s32[] %one.0)
  %xs.0 = f32[100,1024]{1,0} get-tuple-element((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %p.1), index=2
  %zero.0 = s32[] constant(0)
  %row.0 = f32[1,1024]{1,0} dynamic-slice(f32[100,1024]{1,0} %xs.0, s32[] %i.0, s32[] %zero.0), dynamic_slice_sizes={1,1024}
  %buf.0 = f32[100,1024]{1,0} get-tuple-element((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %p.1), index=1
  %upd.0 = f32[100,1024]{1,0} dynamic-update-slice(f32[100,1024]{1,0} %buf.0, f32[1,1024]{1,0} %row.0, s32[] %i.0, s32[] %zero.0)
  ROOT %tup.1 = (s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) tuple(s32[] %next.0, f32[100,1024]{1,0} %upd.0, f32[100,1024]{1,0} %xs.0)
}

%cond.2 (p.2: (s32[], f32[100,1024], f32[100,1024])) -> pred[] {
  %p.2 = (s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) parameter(0)
  %j.0 = s32[] get-tuple-element((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %p.2), index=0
  %n.0 = s32[] constant(100)
  ROOT %lt.1 = pred[] compare(s32[] %j.0, s32[] %n.0), direction=LT
}

ENTRY %main.2 (Arg_0.1: f32[100,1024], Arg_1.2: f32[100,1024]) -> f32[100,1024] {
  %Arg_0.1 = f32[100,1024]{1,0} parameter(0)
  %Arg_1.2 = f32[100,1024]{1,0} parameter(1)
  %z.0 = s32[] constant(0)
  %init.1 = (s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) tuple(s32[] %z.0, f32[100,1024]{1,0} %Arg_0.1, f32[100,1024]{1,0} %Arg_1.2)
  %loop.1 = (s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) while((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %init.1), condition=%cond.2, body=%body.2, metadata={op_name="jit(step)/jit(main)/jvp(__scanlayer_1__)/while"}
  ROOT %out.1 = f32[100,1024]{1,0} get-tuple-element((s32[], f32[100,1024]{1,0}, f32[100,1024]{1,0}) %loop.1), index=1
}
"""


def test_scan_body_slices_charged_at_slice_granularity():
    """The in-place DUS / sliced-read discounts: a 100-trip scan over a
    400 KB carry must charge ~slice-sized traffic per trip (XLA's
    aliasing convention), not re-stream both whole buffers — and the
    while site itself charges nothing (its body is already charged)."""
    rep = costmodel.attribute(DUS_HLO, ())
    row = 1024 * 4                     # one f32[1,1024] slice
    # per trip: DS reads a row (src discounted to the slice), DUS
    # writes a row (aliased buffer discounted both sides) — so the
    # whole loop's executed bytes stay within a few hundred KB, where
    # the undiscounted charge would be ~160 MB
    assert rep["bytes_per_step"] < 100 * 10 * row
    assert rep["while_trips"] == {"loop.1": 100}


def test_loop_body_plumbing_inherits_the_while_region():
    """A scan body's carry plumbing carries no layer op_name of its
    own; it must inherit the region of the `while` that runs it (the
    layer whose named_scope the scan lowered under), not pile up in
    _unattributed."""
    rep = costmodel.attribute(DUS_HLO, {"__scanlayer_1__"})
    scan = rep["regions"]["__scanlayer_1__"]
    assert scan["bytes"] > 0 and scan["flops"] > 0
    un = rep["regions"].get("_unattributed",
                            {"bytes": 0.0, "flops": 0.0})
    # entry-level init/unpack may stay unattributed; the trip-amortized
    # body traffic must not
    assert un["bytes"] < scan["bytes"]


def test_roofline_verdict_pins():
    """The ISSUE's acceptance pins: elementwise = memory-bound, matmul =
    compute-bound, against peaks whose ridge sits between them."""
    rep = costmodel.attribute(SYNTH_HLO, {"__mm_1__", "__ew_1__"})
    mm, ew = rep["regions"]["__mm_1__"], rep["regions"]["__ew_1__"]
    mm_v = costmodel.roofline(mm["flops"], mm["bytes"], PEAKS)
    ew_v = costmodel.roofline(ew["flops"], ew["bytes"], PEAKS)
    assert mm_v["bound"] == "compute"
    assert ew_v["bound"] == "memory"
    assert ew_v["intensity"] == pytest.approx(1 / 12, rel=1e-3)
    # peak-bound time: the memory-bound region is charged at bandwidth
    assert ew_v["time_est_s"] == pytest.approx(
        ew["bytes"] / PEAKS["bw"])
    assert mm_v["time_est_s"] == pytest.approx(
        mm["flops"] / PEAKS["flops"])


def test_mfu_shared_implementation():
    # 1e9 executed FLOPs in 1 ms on a 1 TFLOP/s chip = 100% MFU
    assert costmodel.mfu(1e9, 1e-3, devices=1,
                         peaks=PEAKS) == pytest.approx(1.0)
    assert costmodel.mfu(1e9, 1e-3, devices=4,
                         peaks=PEAKS) == pytest.approx(0.25)


def test_detect_peaks_has_ridge_and_flag_override():
    from paddle_tpu.utils import FLAGS

    p = costmodel.detect_peaks()
    assert p["flops"] > 0 and p["bw"] > 0
    assert p["ridge"] == pytest.approx(p["flops"] / p["bw"])
    saved_f = FLAGS.get("roofline_peak_flops")
    saved_b = FLAGS.get("roofline_peak_gbps")
    FLAGS.set("roofline_peak_flops", 123e12)
    FLAGS.set("roofline_peak_gbps", 456.0)
    try:
        q = costmodel.detect_peaks()
        assert q["flops"] == pytest.approx(123e12)
        assert q["bw"] == pytest.approx(456e9)
        assert q["source"] == "flag"
    finally:
        FLAGS.set("roofline_peak_flops", saved_f)
        FLAGS.set("roofline_peak_gbps", saved_b)


# ------------------------------------------------------ real compiled step
def _tiny_trainer(seed=0):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.data.feeder import DataFeeder, dense_vector, \
        integer_value
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.trainer.trainer import Trainer

    with config_scope():
        x = dsl.data("x", dense_vector(8))
        lab = dsl.data("label", integer_value(2))
        h = dsl.fc(x, size=16, act=dsl.TanhActivation())
        p = dsl.fc(h, size=2, act=dsl.SoftmaxActivation())
        cost = dsl.classification_cost(p, lab)
        cfg = dsl.topology(cost)
    tr = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="momentum", momentum=0.9, learning_rate=0.05),
        seed=seed)
    feeder = DataFeeder([("x", dense_vector(8)),
                         ("label", integer_value(2))])
    return tr, feeder


def _feed(feeder, n=4):
    rng = np.random.RandomState(0)
    return feeder.convert([(rng.randn(8).astype(np.float32),
                            int(rng.randint(0, 2))) for _ in range(n)])


@pytest.fixture
def tiny():
    tr, feeder = _tiny_trainer()
    return tr, _feed(feeder)


def test_analyze_trainer_step_attributes_real_layers(tiny):
    tr, feed = tiny
    rep = costmodel.analyze_trainer_step(tr, feed)
    assert rep is not None
    regions = {r["region"]: r for r in rep["regions"]}
    # the named_scope threading: both fc layers and the optimizer scope
    # come back as regions of the compiled step
    fc = [n for n in regions if n.startswith("__fc_")]
    assert len(fc) == 2
    assert "optimizer" in regions
    # forward AND backward of a trained layer land in its region
    assert any(regions[n]["bwd_frac"] > 0 for n in fc)
    # per-region FLOPs sum to the whole-step total within tolerance
    # (regions are not truncated here: the model has few layers)
    assert rep["regions_elided"] == 0
    total = sum(r["flops"] for r in rep["regions"])
    assert total == pytest.approx(rep["flops_per_step"], rel=1e-6)
    # and the parsed total reconciles against XLA's own cost analysis
    assert rep["flop_agreement"] is not None
    assert 0.5 <= rep["flop_agreement"] <= 1.5
    # every region carries a verdict against the detected peaks
    assert all(r["bound"] in ("compute", "memory")
               for r in rep["regions"])
    assert abs(sum(r["share"] for r in rep["regions"]) - 1.0) < 0.01


def test_analyze_does_not_train(tiny):
    """Observability must not advance training: on a trainer whose step
    is already built, analysis runs NO extra batch — params/opt state
    objects and the step counter are untouched."""
    from paddle_tpu.observe import REGISTRY

    tr, feed = tiny
    tr.train_one_batch(feed)
    params, opt = tr.params, tr.opt_state
    steps = REGISTRY.counter("train_steps").value()
    rep = costmodel.analyze_trainer_step(tr, feed)
    assert rep is not None
    assert tr.params is params and tr.opt_state is opt
    assert REGISTRY.counter("train_steps").value() == steps


def test_analyze_declines_without_raising():
    """A report is an artifact field, never a crash: a stack that
    cannot be analysed reads None, with one warning."""
    class Broken:
        network = None

        def train_one_batch(self, feed):
            raise RuntimeError("no backend")

    from paddle_tpu.utils.logger import reset_warn_once

    reset_warn_once()
    assert costmodel.analyze_trainer_step(Broken(), {}) is None


def test_dump_report_roundtrip(tiny, tmp_path):
    import json

    tr, feed = tiny
    rep = costmodel.analyze_trainer_step(tr, feed)
    path = str(tmp_path / "roofline.json")
    costmodel.dump_report(rep, path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["regions"] == rep["regions"]
    assert doc["peaks"]["ridge"] > 0


# ------------------------------------------------------- memory accounting
def test_memory_account_attributes_known_pytrees(tiny):
    import gc

    tr, feed = tiny
    tr.train_one_batch(feed)
    omem.reset_peak()
    # live_arrays() sees the whole process: collect earlier tests'
    # dropped trainers so the snapshot is THIS trainer's footprint
    gc.collect()
    snap = omem.account(tr, feed)
    cats = snap["categories"]
    assert cats["params"] > 0
    assert cats["opt_state"] > 0          # momentum slots
    assert cats["data"] > 0
    # the ISSUE's acceptance bar: categories account for >= 90% of the
    # in-use bytes after a step
    assert snap["attributed_frac"] >= 0.9
    assert snap["in_use_bytes"] >= sum(
        v for k, v in cats.items() if k != "other")
    assert snap["peak_bytes"] >= snap["in_use_bytes"]
    assert snap["source"] in ("device", "live_arrays")


def test_memory_sample_publishes_gauges(tiny):
    tr, feed = tiny
    tr.train_one_batch(feed)
    snap = omem.sample(tr, feed)
    assert REGISTRY.gauge("hbm_in_use_bytes").value() \
        == snap["in_use_bytes"]
    assert REGISTRY.gauge("hbm_peak_bytes").value() == snap["peak_bytes"]
    cat = REGISTRY.gauge("hbm_category_bytes")
    for name, nbytes in snap["categories"].items():
        assert cat.value(category=name) == nbytes


def test_memory_peak_is_running_max_on_statless_backends():
    omem.reset_peak()
    a = omem.account()
    if a["source"] != "live_arrays":
        pytest.skip("backend reports allocator stats")
    # allocate, sample, free: the peak must not decay with the in-use
    import jax.numpy as jnp

    big = jnp.zeros((256, 1024), jnp.float32)
    big.block_until_ready()
    with_big = omem.account()
    del big
    after = omem.account()
    assert with_big["peak_bytes"] >= with_big["in_use_bytes"]
    assert after["peak_bytes"] >= with_big["in_use_bytes"] \
        - a["in_use_bytes"]


def test_trainer_pass_boundary_samples_memory_gauges(tmp_path):
    """The trainer's once-per-pass observability hook: with a metrics
    sink attached the HBM gauges are populated at the pass boundary;
    the step hot path itself never samples."""
    from paddle_tpu import observe
    from paddle_tpu.utils import FLAGS

    tr, feeder = _tiny_trainer()

    def reader():
        rng = np.random.RandomState(1)
        for _ in range(3):
            yield [(rng.randn(8).astype(np.float32),
                    int(rng.randint(0, 2))) for _ in range(4)]

    saved = FLAGS.get("save_dir")
    FLAGS.set("save_dir", "")
    observe.attach(str(tmp_path / "m.jsonl"), interval_s=999)
    try:
        tr.train(reader, num_passes=1, feeder=feeder)
    finally:
        observe.stop_global()
        FLAGS.set("save_dir", saved)
    assert REGISTRY.gauge("hbm_in_use_bytes").value() > 0
    assert REGISTRY.gauge("hbm_peak_bytes").value() > 0
    assert REGISTRY.gauge("hbm_category_bytes").value(
        category="params") > 0


def test_trainer_roofline_dump_flag_writes_report(tmp_path):
    """--roofline_dump: the one-shot attributed cost report of the
    compiled step lands at the end of pass 0."""
    import json

    from paddle_tpu.utils import FLAGS

    tr, feeder = _tiny_trainer()
    path = str(tmp_path / "roofline.json")

    def reader():
        rng = np.random.RandomState(1)
        for _ in range(2):
            yield [(rng.randn(8).astype(np.float32),
                    int(rng.randint(0, 2))) for _ in range(4)]

    saved_dump = FLAGS.get("roofline_dump")
    saved_dir = FLAGS.get("save_dir")
    FLAGS.set("roofline_dump", path)
    FLAGS.set("save_dir", "")
    try:
        tr.train(reader, num_passes=1, feeder=feeder)
    finally:
        FLAGS.set("roofline_dump", saved_dump)
        FLAGS.set("save_dir", saved_dir)
    with open(path) as f:
        doc = json.load(f)
    assert doc["regions"]
    assert any(r["region"].startswith("__fc_") for r in doc["regions"])


def test_tree_bytes():
    import jax.numpy as jnp

    assert omem.tree_bytes(None) == 0
    tree = {"a": jnp.zeros((4, 4), jnp.float32),
            "b": [jnp.zeros((2,), jnp.bfloat16)]}
    assert omem.tree_bytes(tree) == 4 * 4 * 4 + 2 * 2


def test_device_stats_never_raises():
    assert omem.device_stats(device=object()) is None
