"""End-to-end conv→BN→ReLU→conv→BN chain: fused ≡ unfused.

Pins the COMPOSED autodiff path of the two conv/BN fusion directions
(interpret mode): the first BN defers its affine+ReLU into the second
conv's input pipeline (round-7 forward fusion), while the second
conv→BN pair keeps the round-6 backward fusion — so one chain op
(``pallas_conv._chain_core``) carries the forward prologue AND the
BN-backward affine through the same Pallas backward-data kernel, with
the recomputed-affine residuals (raw z, never the normalized
activation) feeding both.  Forward values, running-stat buffer updates,
and gradients through every parameter must match the fully unfused
composition; eval mode must be the exact composition.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.layers.conv import DeferredBN
from paddle_tpu.layers.network import NeuralNetwork
from paddle_tpu.ops import nn_ops, pallas_conv

EPS = 1e-5


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _build_chain(channels=64, img_sz=6):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector

    with config_scope():
        img = dsl.data("image", dense_vector(channels * img_sz * img_sz),
                       height=img_sz, width=img_sz)
        c1 = dsl.img_conv(img, filter_size=3, num_filters=channels,
                          stride=1, padding=1, num_channels=channels,
                          act=dsl.LinearActivation(), name="c1")
        bn1 = dsl.batch_norm(c1, act=dsl.ReluActivation(), name="bn1")
        c2 = dsl.img_conv(bn1, filter_size=3, num_filters=channels,
                          stride=1, padding=1, num_channels=channels,
                          act=dsl.LinearActivation(), name="c2")
        bn2 = dsl.batch_norm(c2, act=dsl.LinearActivation(), name="bn2")
        cfg = dsl.topology(bn2)
    return NeuralNetwork(cfg)


def _run(net, params, feed, buffers, fused, training=True):
    sf, sb = net._bn_conv_fuse, net._conv_bn_fuse
    net._bn_conv_fuse = sf if fused else {}
    net._conv_bn_fuse = sb if fused else {}
    try:
        return net.forward(params, feed, dict(buffers),
                           is_training=training)
    finally:
        net._bn_conv_fuse, net._conv_bn_fuse = sf, sb


def test_chain_peephole_assignment():
    """bn1 defers forward into c2; the round-6 pair {bn2: c2} survives
    and becomes the chain op (its conv consumes the deferred affine);
    the round-6 pair {bn1: c1} is evicted because bn1 no longer
    materializes an output to fuse backward through."""
    net = _build_chain()
    assert net._bn_conv_fuse == {"c2": "bn1"}
    assert net._conv_bn_fuse == {"bn2": "c2"}
    # the chain gate itself passes for this shape
    assert pallas_conv.fused_chain_ok(6, 6, 64, 64)


def test_chain_forward_and_buffers_match_unfused(rng):
    net = _build_chain()
    params = net.init_params(seed=1)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(4, 64 * 6 * 6).astype(np.float32))}
    v1, b1 = _run(net, params, feed, buffers, True)
    v0, b0 = _run(net, params, feed, buffers, False)
    # in the fused lowering: c2 is executed inside bn2's chain op and
    # bn1 only publishes its affine
    assert "c2" not in v1 and "c2" in v0
    assert isinstance(v1["bn1"], DeferredBN)
    np.testing.assert_allclose(np.asarray(v1["bn2"]),
                               np.asarray(v0["bn2"]),
                               rtol=3e-5, atol=3e-5)
    for k in sorted(b0):        # bn1 AND bn2 running stats both update
        np.testing.assert_allclose(np.asarray(b1[k]), np.asarray(b0[k]),
                                   rtol=3e-5, atol=3e-5, err_msg=k)


def test_chain_gradients_match_unfused(rng):
    """The composed fwd-fusion × bwd-fusion backward: dz1 comes out of
    the chain kernel's prologue tail (recomputed affine + mask from the
    raw z residual), dscale/dbias of BOTH BNs and both conv weights via
    the one-pass reductions — all must equal plain autodiff of the
    unfused graph."""
    net = _build_chain()
    params = net.init_params(seed=2)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(4, 64 * 6 * 6).astype(np.float32))}

    def loss(params, fused):
        values, _ = _run(net, params, feed, buffers, fused)
        return jnp.sum(values["bn2"] ** 2)

    g1 = jax.grad(lambda p: loss(p, True))(params)
    g0 = jax.grad(lambda p: loss(p, False))(params)
    for k in sorted(g0):
        # conv biases feeding a BN are analytically gradient-free (the
        # mean subtracts them) — both sides are f32 noise around 0
        tol = dict(rtol=3e-4, atol=2e-3) if k.endswith(".wbias") \
            else dict(rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   err_msg=k, **tol)


def test_chain_eval_mode_exact(rng):
    net = _build_chain()
    params = net.init_params(seed=3)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(2, 64 * 6 * 6).astype(np.float32))}
    v1, _ = _run(net, params, feed, buffers, True, training=False)
    v0, _ = _run(net, params, feed, buffers, False, training=False)
    np.testing.assert_allclose(np.asarray(v1["bn2"]),
                               np.asarray(v0["bn2"]),
                               rtol=1e-6, atol=1e-6)


def test_chain_op_level_matches_composition(rng):
    """conv2d_bn(in_affine=...) against the hand-written composition:
    relu(a·z + c) → conv+cb → train-mode BN, fwd + stats + grads."""
    n, h, w, cin, cout = 2, 5, 7, 64, 64
    z = jnp.asarray(rng.randn(n, h, w, cin).astype(np.float32)) * 0.5
    a = jnp.asarray(rng.rand(cin).astype(np.float32) + 0.5)
    c = jnp.asarray(rng.randn(cin).astype(np.float32)) * 0.3
    wt = jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32)) * 0.1
    cb = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
    scale = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.2
    rm = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
    rv = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)

    def fused(z, a, c, wt, cb, scale, bias):
        return nn_ops.conv2d_bn(z, wt, cb, scale, bias, rm, rv, eps=EPS,
                                is_training=True, padding=1,
                                in_affine=(a, c, "relu"))

    def ref(z, a, c, wt, cb, scale, bias):
        x = jax.nn.relu(z * a + c)
        z2 = nn_ops.conv2d(x, wt, stride=1, padding=1) + cb
        m = jnp.mean(z2, (0, 1, 2))
        v = jnp.maximum(jnp.mean(jnp.square(z2), (0, 1, 2)) - m * m, 0.0)
        y = (z2 - m) * jax.lax.rsqrt(v + EPS) * scale + bias
        return y, 0.9 * rm + 0.1 * m, 0.9 * rv + 0.1 * v

    args = (z, a, c, wt, cb, scale, bias)
    for g, r in zip(fused(*args), ref(*args)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=3e-5, atol=3e-5)
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))
    g1 = jax.grad(lambda *ar: jnp.sum(fused(*ar)[0] * cot),
                  argnums=tuple(range(7)))(*args)
    g0 = jax.grad(lambda *ar: jnp.sum(ref(*ar)[0] * cot),
                  argnums=tuple(range(7)))(*args)
    names = ["dz", "da", "dc", "dw", "dcb", "dscale", "dbias"]
    for name, gf, gr in zip(names, g1, g0):
        tol = dict(rtol=3e-4, atol=1e-3) if name == "dcb" \
            else dict(rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   err_msg=name, **tol)


#: bfloat16 operands against the float32 composition on the same values:
#: a few roundings of 2^-9, the worst element within 2 % of the largest
#: (tests/test_pallas_conv.py::BF16_TOL says why)
BF16_TOL = 2e-2


@pytest.mark.parametrize("shape", [
    (2, 5, 7, 64, 64),      # 64 channels: nine taps a product
    (2, 4, 4, 128, 128),    # 128: nine products
    (3, 7, 7, 128, 64),     # a 7×7 map, Cin ≠ Cout
])
def test_chain_kernel_in_bf16_matches_the_composition(rng, shape):
    """conv_bn_fwd then conv_bn_chain_bwd (``_chain_core``) on bfloat16
    z1 and w: the value and the gradients wrt z1, a1, c1, w, scale and
    bias.  The conv bias's gradient is analytically 0 (compared at
    float32 above)."""
    n, h, w, cin, cout = shape
    z = jnp.asarray(rng.randn(n, h, w, cin).astype(np.float32)) * 0.5
    a = jnp.asarray(rng.rand(cin).astype(np.float32) + 0.5)
    c = jnp.asarray(rng.randn(cin).astype(np.float32)) * 0.3
    wt = jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32)) * 0.1
    cb = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
    scale = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.2
    zb, wb = z.astype(jnp.bfloat16), wt.astype(jnp.bfloat16)
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))

    def fused(z, a, c, w, s, b):
        y, _m, _v = pallas_conv._chain_core(z, a, c, w, cb, s, b, EPS, True)
        return y.astype(jnp.float32)

    def comp(z, a, c, w, s, b):
        x = jax.nn.relu(z.astype(jnp.float32) * a + c)
        z2 = nn_ops.conv2d(x, w.astype(jnp.float32), stride=1,
                           padding=1) + cb
        m = jnp.mean(z2, (0, 1, 2))
        v = jnp.mean(jnp.square(z2), (0, 1, 2)) - m * m
        return (z2 - m) * jax.lax.rsqrt(v + EPS) * s + b

    res = []
    for fn in (fused, comp):
        y, vjp = jax.vjp(fn, zb, a, c, wb, scale, bias)
        res.append((y, *vjp(cot)))
    for name, g, r in zip(["y", "dz1", "da1", "dc1", "dw", "dscale",
                           "dbias"], *res):
        got = np.asarray(jnp.asarray(g, jnp.float32))
        want = np.asarray(jnp.asarray(r, jnp.float32))
        err = np.max(np.abs(got - want)) / np.max(np.abs(want))
        assert err <= BF16_TOL, (name, err)
