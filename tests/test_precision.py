"""Mixed-precision training policy + int8 quantized serving (round 12).

Covers the ``--precision`` tentpole end to end:

- dynamic loss scaling unit semantics (grow / backoff / floor / skip)
  against ``optimizer/loss_scale.py`` directly;
- trainer integration: ``--precision=fp32`` reproduces the default
  trajectory byte-for-byte, bf16 keeps fp32 master weights + optimizer
  state, a seeded overflow skips the step bit-identically with the
  ``observe`` gauge/counter matching, and the scale grows on schedule;
- bf16-vs-fp32 convergence: quick-lane LSTM within 2% final loss, a
  ResNet slice on the slow lane;
- int8 weights-only serving artifacts: per-channel dequant error bound,
  manifest v2 schema, v1 backward compatibility, output closeness;
- bfloat16 feed round-trip through ``DataFeeder`` → export → loader
  (the ``core/dtypes.np_dtype`` name-mapping satellite).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.config import dsl
from paddle_tpu.config.dsl import config_scope
from paddle_tpu.config.model_config import OptimizationConfig
from paddle_tpu.core.dtypes import np_dtype
from paddle_tpu.data.feeder import (DataFeeder, dense_vector,
                                    integer_value,
                                    integer_value_sequence)
from paddle_tpu.layers import NeuralNetwork
from paddle_tpu.optimizer import loss_scale as ls
from paddle_tpu.trainer.trainer import Trainer
from paddle_tpu.utils import FLAGS

PREC_FLAGS = ("precision", "loss_scale_init", "loss_scale_growth_interval",
              "use_bf16", "bf16_activations", "save_dir", "prefetch_depth")


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {k: FLAGS.get(k) for k in PREC_FLAGS}
    yield
    for k, v in saved.items():
        FLAGS.set(k, v)


def _fc_trainer(precision="", seed=0, lr=1e-2):
    with config_scope():
        img = dsl.data_layer("x", dense_vector(16))
        lbl = dsl.data_layer("label", integer_value(4))
        h = dsl.fc_layer(img, size=32, act=dsl.ReluActivation())
        pred = dsl.fc_layer(h, size=4, act=dsl.SoftmaxActivation(),
                            name="pred")
        cfg = dsl.topology(dsl.classification_cost(pred, lbl))
    net = NeuralNetwork(cfg)
    oc = OptimizationConfig(learning_method="adam", learning_rate=lr,
                            precision=precision)
    return Trainer(net, opt_config=oc, seed=seed)


def _fc_feed(rng, b=8):
    return {"x": jnp.asarray(rng.randn(b, 16).astype(np.float32)),
            "label": jnp.asarray(rng.randint(0, 4, (b,)).astype(np.int32))}


def _bytes(tree):
    return {k: np.asarray(v).tobytes()
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------------- loss-scale unit
def test_loss_scale_grows_after_interval():
    s = ls.LossScaleState(jnp.asarray(8.0), jnp.asarray(0, jnp.int32),
                          jnp.asarray(0, jnp.int32))
    s = ls.update(s, jnp.asarray(True), growth_interval=2)
    assert float(s.scale) == 8.0 and int(s.growth_count) == 1
    s = ls.update(s, jnp.asarray(True), growth_interval=2)
    assert float(s.scale) == 16.0 and int(s.growth_count) == 0
    assert int(s.skipped_total) == 0


def test_loss_scale_backoff_floor_and_skip_count():
    s = ls.LossScaleState(jnp.asarray(4.0), jnp.asarray(7, jnp.int32),
                          jnp.asarray(0, jnp.int32))
    s = ls.update(s, jnp.asarray(False), growth_interval=100)
    assert float(s.scale) == 2.0
    assert int(s.growth_count) == 0      # overflow resets the streak
    assert int(s.skipped_total) == 1
    for _ in range(5):
        s = ls.update(s, jnp.asarray(False), growth_interval=100)
    assert float(s.scale) == 1.0         # floored, never 0
    assert int(s.skipped_total) == 6


def test_loss_scale_growth_is_capped():
    # without the cap the f32 scale eventually overflows to inf, after
    # which backoff (inf*0.5) can never recover — permanent stall
    s = ls.LossScaleState(jnp.asarray(ls.MAX_SCALE),
                          jnp.asarray(0, jnp.int32),
                          jnp.asarray(0, jnp.int32))
    s = ls.update(s, jnp.asarray(True), growth_interval=1)
    assert float(s.scale) == ls.MAX_SCALE       # clamped, not doubled
    s = ls.update(s, jnp.asarray(False), growth_interval=1)
    assert float(s.scale) == ls.MAX_SCALE / 2   # backoff still works


def test_unscale_returns_fp32_and_divides():
    grads = {"w": jnp.asarray([2.0, 4.0], jnp.bfloat16)}
    out = ls.unscale(grads, jnp.asarray(2.0))
    assert out["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out["w"]), [1.0, 2.0])


def test_select_keeps_old_state_bit_identical():
    old = {"w": jnp.asarray([1.25, -3.5])}
    new = {"w": jnp.asarray([9.0, 9.0])}
    kept = ls.select(jnp.asarray(False), new, old)
    assert np.asarray(kept["w"]).tobytes() == \
        np.asarray(old["w"]).tobytes()
    taken = ls.select(jnp.asarray(True), new, old)
    np.testing.assert_array_equal(np.asarray(taken["w"]),
                                  np.asarray(new["w"]))


def test_all_finite_flags_inf_and_nan():
    assert bool(ls.all_finite({"a": jnp.ones(3), "b": jnp.zeros(2)}))
    assert not bool(ls.all_finite({"a": jnp.asarray([1.0, np.inf])}))
    assert not bool(ls.all_finite({"a": jnp.asarray([np.nan])}))


# --------------------------------------------------- trainer integration
def test_fp32_flag_reproduces_default_trajectory_byte_for_byte():
    rng = np.random.RandomState(0)
    feeds = [_fc_feed(rng) for _ in range(3)]
    t_default = _fc_trainer()                 # precision unset -> fp32
    FLAGS.set("precision", "fp32")            # explicit flag
    t_explicit = _fc_trainer()
    for f in feeds:
        t_default.train_one_batch(dict(f))
        t_explicit.train_one_batch(dict(f))
    assert _bytes(t_default.params) == _bytes(t_explicit.params)
    assert _bytes(t_default.opt_state) == _bytes(t_explicit.opt_state)


def test_bf16_master_weights_and_opt_state_stay_fp32():
    rng = np.random.RandomState(1)
    t = _fc_trainer(precision="bf16")
    for _ in range(2):
        t.train_one_batch(_fc_feed(rng))
    for leaf in jax.tree_util.tree_leaves(t.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(t.opt_state):
        assert leaf.dtype in (jnp.float32, jnp.int32)


def test_overflow_skips_step_backs_off_and_counts(monkeypatch):
    from paddle_tpu import observe

    FLAGS.set("loss_scale_init", 1024.0)
    rng = np.random.RandomState(2)
    t = _fc_trainer(precision="bf16")
    good = _fc_feed(rng)
    t.train_one_batch(dict(good))                   # warm, finite
    assert int(t._ls_state.skipped_total) == 0
    p0 = _bytes(t.params)
    o0 = _bytes(t.opt_state)
    bad = {"x": jnp.full((8, 16), np.inf, jnp.float32),
           "label": good["label"]}
    t.train_one_batch(bad)                          # seeded overflow
    assert _bytes(t.params) == p0, "skipped step mutated params"
    assert _bytes(t.opt_state) == o0, "skipped step mutated opt state"
    assert float(t._ls_state.scale) == 512.0        # backed off 0.5x
    assert int(t._ls_state.skipped_total) == 1
    t._sync_precision_metrics()
    assert observe.gauge("loss_scale").value() == 512.0
    assert observe.counter(
        "loss_scale_skipped_steps_total").value() == 1.0
    # a following finite step applies normally at the reduced scale
    t.train_one_batch(dict(good))
    assert _bytes(t.params) != p0
    assert int(t._ls_state.skipped_total) == 1


def test_scale_grows_through_trainer_steps():
    FLAGS.set("loss_scale_init", 4.0)
    FLAGS.set("loss_scale_growth_interval", 2)
    rng = np.random.RandomState(3)
    t = _fc_trainer(precision="bf16")
    t.train_one_batch(_fc_feed(rng))
    assert float(t._ls_state.scale) == 4.0
    t.train_one_batch(_fc_feed(rng))
    assert float(t._ls_state.scale) == 8.0          # grew after 2 steps


def test_loss_scale_persists_through_checkpoint(tmp_path):
    rng = np.random.RandomState(4)
    FLAGS.set("loss_scale_init", 256.0)
    t = _fc_trainer(precision="bf16")
    t.train_one_batch(_fc_feed(rng))
    bad = {"x": jnp.full((8, 16), np.inf, jnp.float32),
           "label": jnp.zeros((8,), jnp.int32)}
    t.train_one_batch(bad)                          # scale -> 128
    d = t.save(str(tmp_path), 0)
    t2 = _fc_trainer(precision="bf16")
    t2.load(d)
    assert float(t2._ls_state.scale) == 128.0
    assert int(t2._ls_state.skipped_total) == 1


def test_precision_dispatch_counter_records_dtype():
    from paddle_tpu import observe

    rng = np.random.RandomState(5)
    t = _fc_trainer(precision="bf16")
    t.train_one_batch(_fc_feed(rng))
    c = observe.counter("precision_dispatch_total")
    assert c.value(op="matmul", dtype="bfloat16") > 0, c.samples()


# --------------------------------------------------------- convergence
def _lstm_trainer_and_feeds(precision, n_steps, seed=0):
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import lstm_text_classifier

    B, T, H, V, E = 8, 12, 32, 200, 16
    cfg = lstm_text_classifier(vocab_size=V, embed_dim=E, hidden_size=H,
                               lstm_num=1, num_classes=2)
    net = NeuralNetwork(cfg)
    t = Trainer(net, opt_config=OptimizationConfig(
        learning_method="adam", learning_rate=5e-3,
        precision=precision), seed=seed)
    rng = np.random.RandomState(7)
    feeds = []
    for _ in range(n_steps):
        ids = rng.randint(0, V, (B, T)).astype(np.int32)
        # learnable rule: label = parity of the first token
        labels = (ids[:, 0] % 2).astype(np.int32)
        feeds.append({"data": SequenceBatch(
            jnp.asarray(ids), jnp.asarray(np.full((B,), T, np.int32))),
            "label": jnp.asarray(labels)})
    return t, feeds


def test_bf16_lstm_final_loss_within_2pct_of_fp32():
    """Quick-lane convergence gate: the same LSTM workload trained under
    --precision=bf16 lands within 2% of the fp32 final loss."""
    n = 30
    finals = {}
    # conftest pins PADDLE_TPU_USE_BF16=0, but force the legacy knob
    # off explicitly so the fp32 baseline is true fp32 even when this
    # file runs outside the pytest env (bench_precision does the same)
    FLAGS.set("use_bf16", False)
    for prec in ("fp32", "bf16"):
        t, feeds = _lstm_trainer_and_feeds(prec, n)
        loss = None
        for f in feeds:
            loss = t.train_one_batch(f)
        finals[prec] = float(loss)
        if prec == "bf16":
            for leaf in jax.tree_util.tree_leaves(t.params):
                assert leaf.dtype == jnp.float32
    rel = abs(finals["bf16"] - finals["fp32"]) / abs(finals["fp32"])
    assert rel < 0.02, finals


@pytest.mark.slow
def test_bf16_resnet_slice_tracks_fp32():
    """Slow lane: a ResNet (cifar family — conv+BN fused pairs active)
    slice trained bf16 tracks the fp32 loss curve within tolerance."""
    from paddle_tpu.models.image import resnet_cifar10

    B, IMG, NCLASS, STEPS = 8, 32, 10, 8
    finals = {}
    FLAGS.set("use_bf16", False)    # true-fp32 baseline (see LSTM test)
    for prec in ("fp32", "bf16"):
        with config_scope():
            img = dsl.data("image", dense_vector(3 * IMG * IMG),
                           height=IMG, width=IMG)
            lab = dsl.data("label", integer_value(NCLASS))
            probs = resnet_cifar10(img, depth=8, num_classes=NCLASS)
            cfg = dsl.topology(dsl.classification_cost(probs, lab))
        net = NeuralNetwork(cfg)
        t = Trainer(net, opt_config=OptimizationConfig(
            learning_method="momentum", momentum=0.9,
            learning_rate=1e-2, precision=prec), seed=0)
        rng = np.random.RandomState(0)
        x = rng.randn(B, 3 * IMG * IMG).astype(np.float32)
        y = rng.randint(0, NCLASS, (B,)).astype(np.int32)
        feed = {"image": jnp.asarray(x), "label": jnp.asarray(y)}
        loss = None
        for _ in range(STEPS):
            loss = t.train_one_batch(dict(feed))
        finals[prec] = float(loss)
        for leaf in jax.tree_util.tree_leaves(t.params):
            assert leaf.dtype == jnp.float32
        # BN running stats updated (the buffers-copy fix keeps them
        # flowing while the skipped-step select stays safe)
        means = [v for k, v in t.buffers.items() if k.endswith(".mean")]
        assert any(float(jnp.abs(m).sum()) > 0 for m in means)
    rel = abs(finals["bf16"] - finals["fp32"]) / abs(finals["fp32"])
    assert rel < 0.1, finals


# ------------------------------------------------------- int8 serving
def test_quantize_int8_per_channel_error_bound():
    from paddle_tpu.serving.export import dequantize_int8, quantize_int8

    rng = np.random.RandomState(0)
    w = (rng.randn(96, 24).astype(np.float32)
         * np.linspace(0.05, 8.0, 24, dtype=np.float32))
    q, scale = quantize_int8(w)
    assert q.dtype == np.int8 and scale.shape == (24,)
    assert int(np.abs(q).max()) <= 127
    deq = dequantize_int8(q, scale, dtype="float32")
    err = np.abs(deq - w).max(axis=0)
    assert np.all(err <= scale / 2 + 1e-7)


def _mlp_net():
    img = dsl.data_layer("img", dense_vector(64))
    lbl = dsl.data_layer("label", integer_value(10))
    h = dsl.fc_layer(img, size=48, act=dsl.ReluActivation())
    pred = dsl.fc_layer(h, size=10, act=dsl.SoftmaxActivation(),
                        name="prediction")
    return dsl.classification_cost(pred, lbl)


def test_int8_artifact_manifest_v2_schema_and_v1_unchanged(tmp_path):
    from paddle_tpu.serving import export_network

    with config_scope():
        cfg = dsl.topology(_mlp_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(3)
    x = np.random.RandomState(0).randn(4, 64).astype(np.float32)

    d1 = str(tmp_path / "v1")
    export_network(net, params, {"img": x}, d1)
    m1 = json.load(open(os.path.join(d1, "manifest.json")))
    assert m1["version"] == 1 and "weights" not in m1
    assert not os.path.exists(os.path.join(d1, "weights.npz"))

    d2 = str(tmp_path / "v2")
    export_network(net, params, {"img": x}, d2, quantize="int8")
    m2 = json.load(open(os.path.join(d2, "manifest.json")))
    assert m2["format"] == "paddle-tpu-serving"
    assert m2["version"] == 2
    w = m2["weights"]
    assert w["scheme"] == "int8-weights-per-channel"
    assert w["file"] == "weights.npz"
    assert w["dequant_dtype"] == "bfloat16"
    names = {e["name"] for e in w["entries"]}
    assert names == set(params)
    for e in w["entries"]:
        assert set(e) == {"name", "shape", "dtype", "quantized", "axis"}
        if e["quantized"]:
            assert e["axis"] == -1 and e["dtype"] == "bfloat16"
        else:
            assert e["dtype"] == "float32"
    # weights-only contract: every >=2-D float tensor quantized, 1-D raw
    npz = np.load(os.path.join(d2, "weights.npz"))
    for e in w["entries"]:
        if e["quantized"]:
            assert npz["q::" + e["name"]].dtype == np.int8
            assert npz["s::" + e["name"]].dtype == np.float32
        else:
            assert ("w::" + e["name"]) in npz


def test_int8_artifact_outputs_close_to_v1(tmp_path):
    from paddle_tpu.serving import ServedModel, export_network

    with config_scope():
        cfg = dsl.topology(_mlp_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(4)
    rng = np.random.RandomState(1)
    x = rng.randn(8, 64).astype(np.float32)

    d1, d2 = str(tmp_path / "fp32"), str(tmp_path / "int8")
    export_network(net, params, {"img": x}, d1)
    export_network(net, params, {"img": x}, d2, quantize="int8")
    a = ServedModel.load(d1)(img=x)["prediction"]
    b = ServedModel.load(d2)(img=x)["prediction"]
    assert b.shape == a.shape
    assert float(np.max(np.abs(a.astype(np.float32)
                               - b.astype(np.float32)))) < 0.05
    # v1 artifact keeps loading with bit-identical outputs
    vals, _ = net.forward(params, {"img": x}, net.init_buffers(),
                          is_training=False, only=["prediction"])
    from paddle_tpu.core.sequence import value_of
    np.testing.assert_array_equal(a, np.asarray(value_of(
        vals["prediction"])))


def test_int8_fp32_dequant_and_batch_poly(tmp_path):
    from paddle_tpu.serving import ServedModel, export_network

    with config_scope():
        cfg = dsl.topology(_mlp_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(5)
    x = np.random.RandomState(2).randn(4, 64).astype(np.float32)
    d = str(tmp_path / "int8fp32")
    export_network(net, params, {"img": x}, d, quantize="int8",
                   dequant_dtype="float32")
    m = json.load(open(os.path.join(d, "manifest.json")))
    assert m["weights"]["dequant_dtype"] == "float32"
    srv = ServedModel.load(d)
    if m["batch_polymorphic"]:
        out = srv(img=np.zeros((3, 64), np.float32))["prediction"]
        assert out.shape == (3, 10)


# --------------------------------------------------- bf16 feed plumbing
def test_np_dtype_maps_bfloat16():
    assert np_dtype("bfloat16") == jnp.bfloat16
    assert np_dtype("float32") == np.float32
    from paddle_tpu.core.dtypes import dtype_name
    assert dtype_name(jnp.bfloat16) == "bfloat16"
    assert dtype_name(np.float32) == "float32"


def test_datafeeder_bf16_dense_roundtrip():
    feeder = DataFeeder([("x", dense_vector(4, dtype="bfloat16")),
                         ("label", integer_value(3))])
    batch = [([0.5, 1.0, 2.0, -1.5], 1), ([1.0, 0.0, 0.25, 3.0], 2)]
    feed = feeder.convert(batch)
    assert feed["x"].dtype == jnp.bfloat16
    assert feed["x"].shape == (2, 4)
    np.testing.assert_allclose(
        np.asarray(feed["x"], np.float32),
        [[0.5, 1.0, 2.0, -1.5], [1.0, 0.0, 0.25, 3.0]])
    assert feed["label"].dtype == jnp.int32


def test_bf16_feed_exports_and_loads(tmp_path):
    """A bfloat16 example feed round-trips through _feed_spec (manifest
    says "bfloat16") and the standalone loader's name->dtype mapping."""
    from paddle_tpu.serving import ServedModel, export_inference_fn

    def fn(feed):
        return {"y": (feed["x"].astype(jnp.float32) * 2.0)}

    x16 = jnp.asarray(np.linspace(-2, 2, 8, dtype=np.float32)
                      .reshape(2, 4)).astype(jnp.bfloat16)
    d = str(tmp_path / "bf16feed")
    export_inference_fn(fn, {"x": x16}, d, ["y"])
    m = json.load(open(os.path.join(d, "manifest.json")))
    assert m["feeds"][0]["dtype"] == "bfloat16"
    srv = ServedModel.load(d)
    out = srv(x=np.ones((2, 4), np.float32))["y"]   # cast by the loader
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.full((2, 4), 2.0))
