"""Serving export artifact — the ``paddle/capi`` answer.

The reference's deployment contract (``paddle/capi/gradient_machine.h:
36-88``): a trained model must run in a process that embeds none of the
training framework.  Here that artifact is a serialized StableHLO module
(weights baked in) + a JSON manifest; the acceptance test loads it in a
FRESH subprocess that never imports the layer engine and demands
bit-identical logits.
"""

import json
import os
import subprocess
import sys

import numpy as np

from paddle_tpu.config import dsl
from paddle_tpu.config.dsl import config_scope
from paddle_tpu.core.sequence import value_of
from paddle_tpu.layers import NeuralNetwork

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mnist_net():
    from paddle_tpu.data.feeder import dense_vector, integer_value

    img = dsl.data_layer("img", dense_vector(784))
    lbl = dsl.data_layer("label", integer_value(10))
    h = dsl.fc_layer(img, size=64, act=dsl.ReluActivation())
    pred = dsl.fc_layer(h, size=10, act=dsl.SoftmaxActivation(),
                        name="prediction")
    return dsl.classification_cost(pred, lbl)


def test_export_and_load_identical_logits(tmp_path):
    with config_scope():
        cfg = dsl.topology(_mnist_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(11)
    rng = np.random.RandomState(1)
    x = rng.randn(8, 784).astype(np.float32)

    from paddle_tpu.serving import ServedModel, export_network

    d = str(tmp_path / "artifact")
    export_network(net, params, {"img": x}, d)
    assert os.path.exists(os.path.join(d, "manifest.json"))
    assert os.path.exists(os.path.join(d, "model.stablehlo"))

    vals, _ = net.forward(params, {"img": x}, net.init_buffers(),
                          is_training=False, only=["prediction"])
    ref = np.asarray(value_of(vals["prediction"]))

    m = ServedModel.load(d)
    np.testing.assert_array_equal(m(img=x)["prediction"], ref)

    # batch-polymorphic artifact serves any batch size
    manifest = json.load(open(os.path.join(d, "manifest.json")))
    if manifest["batch_polymorphic"]:
        x2 = rng.randn(3, 784).astype(np.float32)
        assert m(img=x2)["prediction"].shape == (3, 10)


def test_fresh_process_never_imports_layer_engine(tmp_path):
    """The capi acceptance bar: identical logits from a process that
    never imports paddle_tpu.layers (or the DSL, or the trainer)."""
    with config_scope():
        cfg = dsl.topology(_mnist_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(11)
    rng = np.random.RandomState(2)
    x = rng.randn(5, 784).astype(np.float32)
    d = str(tmp_path / "artifact")

    from paddle_tpu.serving import export_network

    export_network(net, params, {"img": x}, d)
    vals, _ = net.forward(params, {"img": x}, net.init_buffers(),
                          is_training=False, only=["prediction"])
    np.save(str(tmp_path / "x.npy"), x)
    np.save(str(tmp_path / "ref.npy"),
            np.asarray(value_of(vals["prediction"])))

    script = f"""
import sys
import jax
import numpy as np
from paddle_tpu.serving.loader import ServedModel
m = ServedModel.load({d!r})
x = np.load({str(tmp_path / 'x.npy')!r})
out = m(img=x)["prediction"]
ref = np.load({str(tmp_path / 'ref.npy')!r})
np.testing.assert_array_equal(out, ref)
banned = [m for m in sys.modules
          if m.startswith(("paddle_tpu.layers", "paddle_tpu.config",
                           "paddle_tpu.trainer", "paddle_tpu.framework",
                           "paddle_tpu.ops"))]
assert not banned, f"loader dragged in framework modules: {{banned}}"
print("SERVED_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SERVED_OK" in r.stdout


def test_loader_rejects_bad_feed_and_future_version(tmp_path):
    with config_scope():
        cfg = dsl.topology(_mnist_net())
    net = NeuralNetwork(cfg)
    params = net.init_params(1)
    x = np.zeros((2, 784), np.float32)
    d = str(tmp_path / "artifact")

    from paddle_tpu.serving import ServedModel, export_network

    export_network(net, params, {"img": x}, d)
    m = ServedModel.load(d)
    import pytest

    with pytest.raises(KeyError):
        m(wrong=x)
    with pytest.raises(ValueError):
        m(img=np.zeros((2, 7), np.float32))

    mpath = os.path.join(d, "manifest.json")
    manifest = json.load(open(mpath))
    manifest["version"] = 99
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(ValueError):
        ServedModel.load(d)


def test_export_transformer_with_flash_attention(tmp_path):
    """The round-5 attention layers survive the serving export: a
    transformer classifier (Pallas flash attention inside) exports to a
    StableHLO artifact and the loader reproduces the framework's
    probabilities on a fixed-shape batch."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models.text import transformer_classifier_cost
    from paddle_tpu.serving import ServedModel, export_network

    with config_scope():
        cfg = dsl.topology(transformer_classifier_cost(
            vocab_size=20, model_dim=16, num_heads=2, num_layers=1,
            ffn_dim=32, max_len=16))
    net = NeuralNetwork(cfg)
    params = net.init_params(7)
    rng = np.random.RandomState(2)
    ids = rng.randint(0, 20, (4, 8)).astype(np.int32)
    lens = np.array([8, 5, 8, 3], np.int32)
    feed = {"data": SequenceBatch(ids, lens)}

    d = str(tmp_path / "artifact")
    export_network(net, params, feed, d)

    vals, _ = net.forward(params, feed, net.init_buffers(),
                          is_training=False, only=["cls"])
    ref = np.asarray(value_of(vals["cls"]))

    m = ServedModel.load(d)
    got = m(data=ids, data_len=lens)["cls"]
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
