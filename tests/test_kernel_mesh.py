"""Pallas kernels under a multi-device mesh: Mosaic refuses GSPMD
partitioning on the chip ("Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map." — four-chip run,
PR 21), so the Trainer names its mesh (``core/device.kernel_mesh``) and
the batch-local dispatch sites shard_map themselves
(``core/device.batch_local``).  The CPU mesh checks what it can: the
wrap is taken, and training through it equals training on one device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.config.model_config import OptimizationConfig
from paddle_tpu.core import device
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.layers.network import NeuralNetwork
from paddle_tpu.models import (lstm_text_classifier,
                               transformer_text_classifier)
from paddle_tpu.trainer.trainer import Trainer


def _losses(cfg, feed, mesh, steps=3, **kw):
    tr = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="adam", learning_rate=1e-2), mesh=mesh, seed=0,
        **kw)
    return [float(tr.train_one_batch(feed)) for _ in range(steps)]


def _text_feed(vocab, b, t, seed=0):
    rng = np.random.RandomState(seed)
    return {"data": SequenceBatch(
                jnp.asarray(rng.randint(0, vocab, (b, t)), jnp.int32),
                jnp.asarray(rng.randint(t // 2, t + 1, (b,)), jnp.int32)),
            "label": jnp.asarray(rng.randint(0, 2, (b,)), jnp.int32)}


@pytest.fixture
def shard_map_calls(monkeypatch):
    calls = []
    real = jax.shard_map

    def spy(f, **kw):
        calls.append(kw["in_specs"])
        return real(f, **kw)

    monkeypatch.setattr(jax, "shard_map", spy)
    return calls


def test_fused_lstm_trains_the_same_on_a_mesh(shard_map_calls):
    cfg = lstm_text_classifier(vocab_size=200, embed_dim=16,
                               hidden_size=128, lstm_num=1, num_classes=2)
    feed = _text_feed(200, 8, 6)
    one = _losses(cfg, feed, device.build_mesh({"data": 1}))
    assert not shard_map_calls          # one device: a plain call
    for axes in ({"data": 4}, {"data": 2, "model": 2}):
        del shard_map_calls[:]
        many = _losses(cfg, feed, device.build_mesh(axes))
        assert shard_map_calls          # the kernel ran per shard
        # steps 2 and 3 depend on the gradients that crossed the
        # shard_map (weights replicated in, cotangents summed back —
        # over `data` only, not doubled by the `model` replicas)
        np.testing.assert_allclose(many, one, rtol=2e-4)


def test_flash_attention_trains_the_same_under_fsdp_on_a_mesh(
        shard_map_calls):
    from paddle_tpu.parallel.rule_tables import zoo_fsdp_rules

    cfg = transformer_text_classifier(
        vocab_size=200, model_dim=32, num_heads=2, num_layers=1,
        ffn_dim=64, num_classes=2, max_len=128, causal=True,
        block_q=128, block_k=128)
    feed = _text_feed(200, 8, 128)
    one = _losses(cfg, feed, device.build_mesh({"data": 1}))
    fsdp = _losses(cfg, feed, device.build_mesh({"data": 4}), fsdp=True,
                   fsdp_rules=zoo_fsdp_rules("transformer"))
    assert shard_map_calls
    np.testing.assert_allclose(fsdp, one, rtol=2e-4)


def test_batch_local_keeps_an_indivisible_batch_whole():
    mesh = device.build_mesh({"data": 4})
    x = jnp.arange(6 * 3, dtype=jnp.float32).reshape(6, 3)   # 6 % 4 != 0
    w = jnp.ones((3,), jnp.float32)
    seen = []

    def fn(x, w):
        seen.append(x.shape)
        return x * w

    with device.kernel_mesh(mesh):
        assert device.local_rows(6) == 6 and device.local_rows(8) == 2
        out = jax.jit(lambda x, w: device.batch_local(
            fn, (x, w), batch_in=(True, False), batch_out=True))(x, w)
    assert seen == [(6, 3)]             # every device runs all six rows
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    assert device.kernel_devices() == 1   # scope closed


def test_conv_bn_pair_is_gated_under_a_mesh():
    """The fused conv→BN pair computes batch statistics inside its
    custom_vjp core: under a mesh it takes the XLA composition under a
    named reason instead of silently normalizing per shard."""
    from paddle_tpu.ops import nn_ops

    x = jnp.ones((4, 8, 8, 64), jnp.float32)
    w = jnp.ones((3, 3, 64, 64), jnp.float32) * 0.01
    ones, zeros = jnp.ones((64,)), jnp.zeros((64,))

    def pair():
        return nn_ops.conv2d_bn(x, w, None, ones, zeros, zeros, ones,
                                is_training=True, padding=1)[0]

    def ticks():
        m = observe.REGISTRY.find("conv_dispatch_total")
        return {(s["labels"]["path"], s["labels"]["reason"]): s["value"]
                for s in (m.samples() if m else ())}

    ref = pair()
    assert ticks() == {("fused", ""): 1.0}
    with device.kernel_mesh(device.build_mesh({"data": 4})):
        got = pair()
    assert ticks()[("unfused", "multi-device mesh (BN statistics span "
                    "the batch)")] == 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
