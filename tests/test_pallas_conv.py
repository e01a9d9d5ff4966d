"""Fused Pallas conv backward-data + BN affine ≡ the unfused path.

The fused conv→BN op (``ops/pallas_conv.py``, the ``hl_cuda_cudnn``
fused conv/BN tier) must be numerically interchangeable with the plain
``lax.conv_general_dilated`` + batch-norm composition it replaces —
forward, running-stat updates, and gradients through every input, across
the 3×3 stride-1 family including edge shapes.  The network-level
peephole must fire exactly on the linear-conv→batch-norm pattern.  Runs
in Pallas interpret mode on CPU (same dispatch gate as hardware).

The round-7 FORWARD fusion (BN affine + ReLU streamed through the
consuming conv's input pipeline — the 3×3 Pallas kernel and the 1×1
GEMM prologue, plus the chain composition with the round-6 backward)
is pinned the same way in the second half of this file: fwd + gradient
equivalence vs the unfused composition, exact-composition fallbacks on
every gate miss (eval mode, C=48/C=96, stride-2), and both kill
switches (--conv_bn_fuse / --conv_bn_fuse_fwd).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.ops import nn_ops, pallas_conv

EPS = 1e-5


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _inputs(rng, n, h, w, cin, cout, with_cb=True):
    x = jnp.asarray(rng.randn(n, h, w, cin).astype(np.float32)) * 0.5
    wt = jnp.asarray(rng.randn(3, 3, cin, cout).astype(np.float32)) * 0.1
    cb = (jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
          if with_cb else None)
    scale = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.2
    rm = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
    rv = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
    return x, wt, cb, scale, bias, rm, rv


def _reference(x, w, cb, scale, bias, rm, rv, momentum=0.9,
               is_training=True):
    """Plain-jax oracle: lax conv + textbook batch norm, autodiffed."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    z = lax.conv_general_dilated(x, w, (1, 1), [(1, 1), (1, 1)],
                                 dimension_numbers=dn)
    if cb is not None:
        z = z + cb
    if not is_training:
        return (z - rm) * lax.rsqrt(rv + EPS) * scale + bias, rm, rv
    m = jnp.mean(z, (0, 1, 2))
    v = jnp.maximum(jnp.mean(jnp.square(z), (0, 1, 2)) - m * m, 0.0)
    y = (z - m) * lax.rsqrt(v + EPS) * scale + bias
    return y, momentum * rm + (1 - momentum) * m, \
        momentum * rv + (1 - momentum) * v


def _assert_close(got, want, rtol=2e-5, atol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol)


# ------------------------------------------------------------- dispatch
def test_dispatch_gate():
    ok = pallas_conv.fusable
    w3 = (3, 3, 64, 64)
    x4 = (2, 8, 8, 64)
    assert ok(x4, w3, 1, [(1, 1), (1, 1)], 1, 1, "NHWC")
    assert ok(x4, w3, 1, "SAME", 1, 1, "NHWC")
    assert ok(x4, w3, (1, 1), 1, (1, 1), 1, "NHWC")
    assert not ok(x4, w3, 2, 1, 1, 1, "NHWC")           # stride
    assert not ok(x4, w3, 1, 0, 1, 1, "NHWC")           # VALID pad
    assert not ok(x4, w3, 1, 1, 2, 1, "NHWC")           # dilation
    assert not ok(x4, w3, 1, 1, 1, 2, "NHWC")           # groups
    assert not ok(x4, (5, 5, 64, 64), 1, 2, 1, 1, "NHWC")  # 5×5
    assert not ok(x4, w3, 1, 1, 1, 1, "NCHW")           # layout
    assert not ok((2, 8, 8, 48), (3, 3, 48, 64), 1, 1, 1, 1,
                  "NHWC")                               # Cin % 64
    assert not ok((2, 8, 8, 64), (3, 3, 64, 48), 1, 1, 1, 1,
                  "NHWC")                               # Cout % 64
    # ResNet-50's whole 3×3 family tiles; a hypothetical giant doesn't
    assert pallas_conv.fused_ok(56, 56, 64, 64)
    assert pallas_conv.fused_ok(28, 28, 128, 128)
    assert pallas_conv.fused_ok(14, 14, 256, 256)
    assert pallas_conv.fused_ok(7, 7, 512, 512)
    assert not pallas_conv.fused_ok(224, 224, 256, 256)  # VMEM


# --------------------------------------------------- fused ≡ reference
@pytest.mark.parametrize("shape", [
    (2, 5, 7, 64, 64),      # odd H/W, the smallest fused channels
    (1, 4, 4, 128, 64),     # Cin ≠ Cout, contracting
    (2, 3, 3, 64, 128),     # expanding, spatial == kernel
])
def test_fused_forward_and_stats_match_reference(rng, shape):
    n, h, w, cin, cout = shape
    args = _inputs(rng, n, h, w, cin, cout)
    assert pallas_conv.fusable((n, h, w, cin), (3, 3, cin, cout),
                               1, 1, 1, 1, "NHWC")
    got = nn_ops.conv2d_bn(*args, eps=EPS, is_training=True, padding=1)
    want = _reference(*args)
    for g, r in zip(got, want):
        _assert_close(g, r)


@pytest.mark.parametrize("with_cb", [True, False])
def test_fused_gradients_match_reference(rng, with_cb):
    n, h, w, cin, cout = (2, 5, 7, 64, 64) if with_cb else (1, 4, 6, 64, 64)
    x, wt, cb, scale, bias, rm, rv = _inputs(rng, n, h, w, cin, cout,
                                             with_cb=with_cb)
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))

    def loss(fn, x, wt, scale, bias, cb=None):
        y, _, _ = fn(x, wt, cb, scale, bias, rm, rv)
        return jnp.sum(y * cot)

    fused = lambda *a: nn_ops.conv2d_bn(*a, eps=EPS, is_training=True,
                                        padding=1)
    args = (x, wt, scale, bias) + ((cb,) if with_cb else ())
    argnums = tuple(range(len(args)))
    g_fused = jax.grad(lambda *a: loss(fused, *a), argnums=argnums)(*args)
    g_ref = jax.grad(lambda *a: loss(_reference, *a), argnums=argnums)(*args)
    # conv bias pre-BN is analytically gradient-free (BN subtracts the
    # mean), so both sides are f32 noise around 0 — compare by atol
    # scaled to the other gradients' magnitude
    names = ["dx", "dw", "dscale", "dbias", "dconv_bias"]
    for name, gf, gr in zip(names, g_fused, g_ref):
        tol = dict(rtol=3e-4, atol=1e-3) if name == "dconv_bias" \
            else dict(rtol=3e-4, atol=3e-5)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   err_msg=name, **tol)


# ------------------------------------------------- fallback equivalence
@pytest.mark.parametrize("shape", [
    (2, 5, 5, 48, 64),      # Cin off-tile → plain path
    (2, 5, 5, 3, 16),       # the resnet_cifar10 stem shapes
])
def test_edge_channels_fall_back_and_match(rng, shape):
    n, h, w, cin, cout = shape
    args = _inputs(rng, n, h, w, cin, cout)
    assert not pallas_conv.fusable((n, h, w, cin), (3, 3, cin, cout),
                                   1, 1, 1, 1, "NHWC")
    got = nn_ops.conv2d_bn(*args, eps=EPS, is_training=True, padding=1)
    want = _reference(*args)
    for g, r in zip(got, want):
        _assert_close(g, r)


def test_eval_mode_matches_composition(rng):
    n, h, w, c = 2, 5, 7, 64
    args = _inputs(rng, n, h, w, c, c)
    got = nn_ops.conv2d_bn(*args, eps=EPS, is_training=False, padding=1)
    want = _reference(*args, is_training=False)
    for g, r in zip(got, want):
        _assert_close(g, r)


def test_fused_matches_under_bf16_policy(rng):
    """The production-default bf16 policy: fused and unfused paths agree
    within bf16 rounding (both compute the conv in bf16)."""
    from paddle_tpu.utils import FLAGS

    FLAGS.set("bf16_activations", True)
    try:
        n, h, w, c = 2, 4, 4, 64
        x, wt, cb, scale, bias, rm, rv = _inputs(rng, n, h, w, c, c)
        y, _, _ = nn_ops.conv2d_bn(x, wt, cb, scale, bias, rm, rv,
                                   eps=EPS, is_training=True, padding=1)
        z = nn_ops.conv2d(x, wt, stride=1, padding=1) + cb
        y2, _, _ = nn_ops.batch_norm(z, scale, bias, rm, rv, eps=EPS,
                                     is_training=True)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y2, np.float32),
                                   rtol=3e-2, atol=3e-2)
    finally:
        FLAGS.set("bf16_activations", False)


# ----------------------------------------------------- network peephole
def _build_net(conv_act=None, filter_size=3, stride=1, padding=1,
               second_consumer=False, channels=64):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector
    from paddle_tpu.layers.network import NeuralNetwork

    img_sz = 6
    with config_scope():
        img = dsl.data("image", dense_vector(channels * img_sz * img_sz),
                       height=img_sz, width=img_sz)
        conv = dsl.img_conv(
            img, filter_size=filter_size, num_filters=channels,
            stride=stride, padding=padding, num_channels=channels,
            act=conv_act or dsl.LinearActivation(), name="c1")
        bn = dsl.batch_norm(conv, act=dsl.ReluActivation(), name="bn1")
        if second_consumer:
            out = dsl.addto([bn, conv], name="sum")
            cfg = dsl.topology(out)
        else:
            cfg = dsl.topology(bn)
    return NeuralNetwork(cfg)


def test_peephole_fires_on_intended_pattern():
    from paddle_tpu.config.dsl import ReluActivation

    assert _build_net()._conv_bn_fuse == {"bn1": "c1"}
    # anything off-pattern must NOT fire
    assert _build_net(conv_act=ReluActivation())._conv_bn_fuse == {}
    assert _build_net(filter_size=5, padding=2)._conv_bn_fuse == {}
    assert _build_net(stride=2)._conv_bn_fuse == {}
    assert _build_net(padding=0)._conv_bn_fuse == {}
    # conv consumed by a second layer keeps its standalone value
    assert _build_net(second_consumer=True)._conv_bn_fuse == {}


def test_peephole_respects_non_layer_consumers():
    """Consumers that read values by name outside layer input lists —
    evaluators here — must block the fusion, or the conv's value would
    be missing from the forward values dict when they look it up."""
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector
    from paddle_tpu.layers.network import NeuralNetwork

    with config_scope():
        img = dsl.data("image", dense_vector(64 * 6 * 6), height=6,
                       width=6)
        conv = dsl.img_conv(img, filter_size=3, num_filters=64, stride=1,
                            padding=1, num_channels=64,
                            act=dsl.LinearActivation(), name="c1")
        bn = dsl.batch_norm(conv, act=dsl.ReluActivation(), name="bn1")
        cfg = dsl.topology(bn)
    cfg.evaluators.append({"type": "value_printer", "name": "vp",
                           "input_layer_name": "c1"})
    assert NeuralNetwork(cfg)._conv_bn_fuse == {}


def test_peephole_network_gradients_match_unfused(rng):
    net = _build_net()
    assert net._conv_bn_fuse == {"bn1": "c1"}
    params = net.init_params(seed=1)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(4, 64 * 6 * 6).astype(np.float32))}

    def run(params, fuse):
        saved = net._conv_bn_fuse
        net._conv_bn_fuse = saved if fuse else {}
        try:
            values, bufs = net.forward(params, feed, dict(buffers),
                                       is_training=True)
        finally:
            net._conv_bn_fuse = saved
        return values, bufs

    v1, b1 = run(params, True)
    v0, b0 = run(params, False)
    # the conv's standalone value is fused away; outputs and the
    # running-stat buffer updates are unchanged
    assert "c1" not in v1 and "c1" in v0
    _assert_close(v1["bn1"], v0["bn1"])
    for k in b0:
        _assert_close(b1[k], b0[k])

    def loss(params, fuse):
        values, _ = run(params, fuse)
        return jnp.sum(values["bn1"] ** 2)

    g1 = jax.grad(lambda p: loss(p, True))(params)
    g0 = jax.grad(lambda p: loss(p, False))(params)
    for k in sorted(g0):
        tol = dict(rtol=3e-4, atol=1e-3) if k.endswith("c1.wbias") \
            else dict(rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   err_msg=k, **tol)


def test_peephole_eval_forward_matches(rng):
    net = _build_net()
    params = net.init_params(seed=2)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(2, 64 * 6 * 6).astype(np.float32))}
    v1, _ = net.forward(params, feed, dict(buffers), is_training=False)
    saved = net._conv_bn_fuse
    net._conv_bn_fuse = {}
    try:
        v0, _ = net.forward(params, feed, dict(buffers),
                            is_training=False)
    finally:
        net._conv_bn_fuse = saved
    _assert_close(v1["bn1"], v0["bn1"])


def test_second_consumer_keeps_conv_value(rng):
    """Off-pattern network (conv feeds BN *and* addto): values flow as
    before — the conv's output is materialized and consumed twice."""
    net = _build_net(second_consumer=True)
    params = net.init_params(seed=3)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(2, 64 * 6 * 6).astype(np.float32))}
    values, _ = net.forward(params, feed, dict(buffers),
                            is_training=True)
    assert "c1" in values and "sum" in values
    assert np.isfinite(np.asarray(values["sum"])).all()


# ====================================================== forward fusion
def _fwd_reference(z, a, c, w, act="relu", conv_bias=None):
    """Plain-jax oracle for the forward fusion: the unfused BN-apply
    formula act(a·z + c) followed by the conv, autodiffed."""
    x = z * a + c
    if act == "relu":
        x = jax.nn.relu(x)
    dn = lax.conv_dimension_numbers(z.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    kh = w.shape[0]
    pad = [(1, 1), (1, 1)] if kh == 3 else [(0, 0), (0, 0)]
    out = lax.conv_general_dilated(x, w, (1, 1), pad,
                                   dimension_numbers=dn)
    return out + conv_bias if conv_bias is not None else out


def _fwd_inputs(rng, n, h, w, cin, cout, kh=3):
    z = jnp.asarray(rng.randn(n, h, w, cin).astype(np.float32)) * 0.5
    wt = jnp.asarray(rng.randn(kh, kh, cin, cout).astype(np.float32)) * 0.1
    a = jnp.asarray(rng.rand(cin).astype(np.float32) + 0.5)
    c = jnp.asarray(rng.randn(cin).astype(np.float32)) * 0.3
    return z, a, c, wt


def test_fwd_dispatch_gate():
    ok = pallas_conv.fusable_fwd
    w3 = (3, 3, 64, 64)
    z4 = (2, 8, 8, 64)
    assert ok(z4, w3, 1, [(1, 1), (1, 1)], 1, 1, "NHWC")
    assert ok(z4, w3, 1, "SAME", 1, 1, "NHWC")
    assert not ok(z4, w3, 2, 1, 1, 1, "NHWC")           # stride
    assert not ok(z4, w3, 1, 0, 1, 1, "NHWC")           # VALID pad
    assert not ok(z4, w3, 1, 1, 2, 1, "NHWC")           # dilation
    assert not ok(z4, w3, 1, 1, 1, 2, "NHWC")           # groups
    assert not ok(z4, (5, 5, 64, 64), 1, 2, 1, 1, "NHWC")  # 5×5
    assert not ok(z4, w3, 1, 1, 1, 1, "NCHW")           # layout
    assert not ok((2, 8, 8, 48), (3, 3, 48, 64), 1, 1, 1, 1,
                  "NHWC")                               # Cin % 64
    assert not ok((2, 8, 8, 96), (3, 3, 96, 64), 1, 1, 1, 1,
                  "NHWC")                               # Cin = 96
    assert not ok((2, 8, 8, 64), (3, 3, 64, 96), 1, 1, 1, 1,
                  "NHWC")                               # Cout = 96
    # ResNet-50's whole 3×3 family tiles for both fwd and chain kernels
    for hw, ch in ((56, 64), (28, 128), (14, 256), (7, 512)):
        assert pallas_conv.fused_fwd_ok(hw, hw, ch, ch)
        assert pallas_conv.fused_chain_ok(hw, hw, ch, ch)
    assert not pallas_conv.fused_fwd_ok(224, 224, 256, 256)   # VMEM
    assert not pallas_conv.fused_chain_ok(224, 224, 256, 256)


def test_gemm_prologue_gate():
    ok = nn_ops._gemm_prologue_ok
    w1 = (1, 1, 48, 64)
    z4 = (2, 8, 8, 48)
    assert ok(z4, w1, 1, 0, 1, 1, "NHWC")       # no %64 rule: plain GEMM
    assert ok(z4, w1, 1, "SAME", 1, 1, "NHWC")
    assert ok(z4, w1, 1, [(0, 0), (0, 0)], 1, 1, "NHWC")
    assert not ok(z4, w1, 2, 0, 1, 1, "NHWC")           # stride
    assert not ok(z4, w1, 1, 1, 1, 1, "NHWC")           # pad
    assert not ok(z4, w1, 1, 0, 1, 2, "NHWC")           # groups
    assert not ok(z4, (3, 3, 48, 64), 1, 0, 1, 1, "NHWC")  # 3×3
    assert not ok(z4, w1, 1, 0, 1, 1, "NCHW")           # layout


@pytest.mark.parametrize("shape", [
    (2, 5, 7, 64, 64),      # odd H/W, the smallest fused channels
    (1, 4, 4, 128, 64),     # Cin ≠ Cout, contracting
    (2, 3, 3, 64, 128),     # expanding, spatial == kernel
])
@pytest.mark.parametrize("act", ["relu", ""])
def test_fused_fwd_matches_reference(rng, shape, act):
    n, h, w, cin, cout = shape
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
    assert pallas_conv.fusable_fwd((n, h, w, cin), (3, 3, cin, cout),
                                   1, 1, 1, 1, "NHWC")
    got = nn_ops.affine_act_conv2d(z, a, c, wt, act=act,
                                   is_training=True, padding=1)
    _assert_close(got, _fwd_reference(z, a, c, wt, act))


def test_fused_fwd_gradients_match_reference(rng):
    n, h, w, cin, cout = 2, 5, 7, 64, 64
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
    cb = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))

    def loss_fused(z, a, c, wt, cb):
        y = nn_ops.affine_act_conv2d(z, a, c, wt, conv_bias=cb,
                                     is_training=True, padding=1)
        return jnp.sum(y * cot)

    def loss_ref(z, a, c, wt, cb):
        return jnp.sum(_fwd_reference(z, a, c, wt, "relu", cb) * cot)

    args = (z, a, c, wt, cb)
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(*args)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(*args)
    for name, gf, gr in zip(["dz", "da", "dc", "dw", "dcb"],
                            g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   err_msg=name, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("cin,cout", [(64, 64), (48, 96)])
def test_fused_fwd_1x1_prologue_matches(rng, cin, cout):
    """The 1×1 GEMM path accepts the affine+ReLU prologue with no
    channel-tile rule (plain dot_general underneath) — fwd + grads."""
    n, h, w = 2, 5, 5
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout, kh=1)
    assert nn_ops._gemm_prologue_ok((n, h, w, cin), (1, 1, cin, cout),
                                    1, 0, 1, 1, "NHWC")
    got = nn_ops.affine_act_conv2d(z, a, c, wt, is_training=True,
                                   padding=0)
    _assert_close(got, _fwd_reference(z, a, c, wt))
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))
    g_fused = jax.grad(
        lambda *ar: jnp.sum(nn_ops.affine_act_conv2d(
            *ar, is_training=True, padding=0) * cot),
        argnums=(0, 1, 2, 3))(z, a, c, wt)
    g_ref = jax.grad(
        lambda *ar: jnp.sum(_fwd_reference(*ar) * cot),
        argnums=(0, 1, 2, 3))(z, a, c, wt)
    for name, gf, gr in zip(["dz", "da", "dc", "dw"], g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   err_msg=name, rtol=3e-4, atol=3e-4)


# ------------------------------------------- fwd gates → exact fallback
@pytest.mark.parametrize("cin,cout", [(48, 64), (96, 96)])
def test_fwd_edge_channels_fall_back_and_match(rng, cin, cout):
    """Off-tile channels through the forward direction take the exact
    unfused composition (and still match it)."""
    n, h, w = 2, 5, 5
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
    assert not pallas_conv.fusable_fwd((n, h, w, cin), (3, 3, cin, cout),
                                       1, 1, 1, 1, "NHWC")
    got = nn_ops.affine_act_conv2d(z, a, c, wt, is_training=True,
                                   padding=1)
    _assert_close(got, _fwd_reference(z, a, c, wt))


def test_fwd_eval_and_stride_fall_back_and_match(rng):
    n, h, w, cin, cout = 2, 6, 6, 64, 64
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
    # eval mode: the exact composition even though the shapes tile
    got = nn_ops.affine_act_conv2d(z, a, c, wt, is_training=False,
                                   padding=1)
    _assert_close(got, _fwd_reference(z, a, c, wt), rtol=1e-6, atol=1e-6)
    # stride-2 never fuses (both kernel families are stride-1)
    x = jax.nn.relu(z * a + c)
    want = nn_ops.conv2d(x, wt, stride=2, padding=1)
    got = nn_ops.affine_act_conv2d(z, a, c, wt, is_training=True,
                                   stride=2, padding=1)
    _assert_close(got, want)


def test_chain_gate_misses_fall_back_and_match(rng):
    """conv2d_bn with an input affine: eval mode and off-tile channels
    materialize the affine exactly and continue as a plain pair — the
    'both directions' gate contract."""
    for cin, training in (((48), True), ((64), False)):
        n, h, w, cout = 2, 5, 5, 64
        z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
        cb = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
        scale = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
        bias = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.2
        rm = jnp.asarray(rng.randn(cout).astype(np.float32)) * 0.1
        rv = jnp.asarray(rng.rand(cout).astype(np.float32) + 0.5)
        got = nn_ops.conv2d_bn(z, wt, cb, scale, bias, rm, rv, eps=EPS,
                               is_training=training, padding=1,
                               in_affine=(a, c, "relu"))
        x = jax.nn.relu(z * a + c)
        want = _reference(x, wt, cb, scale, bias, rm, rv,
                          is_training=training)
        for g, r in zip(got, want):
            _assert_close(g, r)


# ---------------------------------------------- fwd peephole + switches
def _build_fwd_net(bn_act=None, filter_size=3, stride=1, padding=1,
                   second_consumer=False, channels=64, out_is_bn=False):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector
    from paddle_tpu.layers.network import NeuralNetwork

    img_sz = 6
    with config_scope():
        img = dsl.data("image", dense_vector(channels * img_sz * img_sz),
                       height=img_sz, width=img_sz)
        conv = dsl.img_conv(
            img, filter_size=3, num_filters=channels, stride=1,
            padding=1, num_channels=channels,
            act=dsl.LinearActivation(), name="c1")
        bn = dsl.batch_norm(conv, act=bn_act or dsl.ReluActivation(),
                            name="bn1")
        if out_is_bn:
            return NeuralNetwork(dsl.topology(bn))
        conv2 = dsl.img_conv(
            bn, filter_size=filter_size, num_filters=channels,
            stride=stride, padding=padding, num_channels=channels,
            act=dsl.ReluActivation(), name="c2")
        if second_consumer:
            out = dsl.addto([conv2, bn], name="sum")
            cfg = dsl.topology(out)
        else:
            cfg = dsl.topology(conv2)
    return NeuralNetwork(cfg)


def test_fwd_peephole_fires_on_intended_pattern():
    from paddle_tpu.config.dsl import SigmoidActivation

    assert _build_fwd_net()._bn_conv_fuse == {"c2": "bn1"}
    # the 1×1 pointwise direction fires too
    assert _build_fwd_net(filter_size=1, padding=0) \
        ._bn_conv_fuse == {"c2": "bn1"}
    # anything off-pattern must NOT fire
    assert _build_fwd_net(stride=2)._bn_conv_fuse == {}
    assert _build_fwd_net(filter_size=5, padding=2)._bn_conv_fuse == {}
    assert _build_fwd_net(
        bn_act=SigmoidActivation())._bn_conv_fuse == {}
    # BN with a second consumer keeps its standalone value
    assert _build_fwd_net(second_consumer=True)._bn_conv_fuse == {}
    # BN as the network output is never deferred
    assert _build_fwd_net(out_is_bn=True)._bn_conv_fuse == {}


def test_fwd_kill_switch_restores_round6_lowering():
    """--conv_bn_fuse_fwd=false must reproduce the exact round-6 maps:
    no deferred BNs, and the conv→BN backward pairs reinstated."""
    from paddle_tpu.utils import FLAGS

    net = _build_fwd_net()
    # fwd fusion claims bn1, which evicts the round-6 {bn1: c1} pair
    assert net._bn_conv_fuse == {"c2": "bn1"}
    assert net._conv_bn_fuse == {}
    FLAGS.set("conv_bn_fuse_fwd", False)
    try:
        net = _build_fwd_net()
        assert net._bn_conv_fuse == {}
        assert net._conv_bn_fuse == {"bn1": "c1"}   # round 6 restored
    finally:
        FLAGS.set("conv_bn_fuse_fwd", True)
    # and the round-6 switch composes: both off → nothing fuses
    FLAGS.set("conv_bn_fuse", False)
    FLAGS.set("conv_bn_fuse_fwd", False)
    try:
        net = _build_fwd_net()
        assert net._bn_conv_fuse == {} and net._conv_bn_fuse == {}
    finally:
        FLAGS.set("conv_bn_fuse", True)
        FLAGS.set("conv_bn_fuse_fwd", True)


def test_fwd_peephole_network_matches_unfused(rng):
    net = _build_fwd_net()
    assert net._bn_conv_fuse == {"c2": "bn1"}
    params = net.init_params(seed=1)
    buffers = net.init_buffers()
    feed = {"image": jnp.asarray(
        rng.randn(4, 64 * 6 * 6).astype(np.float32))}

    def run(params, fuse, training=True):
        saved = net._bn_conv_fuse
        net._bn_conv_fuse = saved if fuse else {}
        try:
            return net.forward(params, feed, dict(buffers),
                               is_training=training)
        finally:
            net._bn_conv_fuse = saved

    v1, b1 = run(params, True)
    v0, b0 = run(params, False)
    # the BN's applied value is fused away (a DeferredBN placeholder
    # remains); outputs and running-stat updates are unchanged
    from paddle_tpu.layers.conv import DeferredBN

    assert isinstance(v1["bn1"], DeferredBN)
    assert not isinstance(v0["bn1"], DeferredBN)
    _assert_close(v1["c2"], v0["c2"])
    for k in b0:
        _assert_close(b1[k], b0[k])

    def loss(params, fuse):
        values, _ = run(params, fuse)
        return jnp.sum(values["c2"] ** 2)

    g1 = jax.grad(lambda p: loss(p, True))(params)
    g0 = jax.grad(lambda p: loss(p, False))(params)
    for k in sorted(g0):
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   err_msg=k, rtol=3e-4, atol=3e-4)

    # eval mode: the forward falls back to the exact composition
    v1, _ = run(params, True, training=False)
    v0, _ = run(params, False, training=False)
    _assert_close(v1["c2"], v0["c2"], rtol=1e-6, atol=1e-6)


# ============================================ bfloat16 operands, by tile
#: The kernels multiply in the weights' dtype and accumulate in float32;
#: the composition they are held to here runs in float32 on the same
#: bfloat16 values.  bfloat16 keeps 8 significant bits (a rounding is
#: 2^-9 of a value) and the fused path rounds x = act(A·z + C), the
#: cotangent's affine and its outputs where the float32 one does not, so
#: they agree to a few roundings: the worst element within 2 % of the
#: largest (a missing tap or a shifted column is off by 10–100 %).
BF16_TOL = 2e-2

BF16_SHAPES = [
    (2, 5, 7, 64, 64),      # 64 channels: nine taps a product
    (2, 4, 4, 128, 128),    # 128: nine products
    (3, 7, 7, 64, 128),     # a 7×7 map, Cin ≠ Cout
]


def _worst(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    assert np.all(np.isfinite(got))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


def _fwd_pair_grads(rng, n, h, w, cin, cout):
    """conv_bn_fwd and conv_bn_fwd_bwd (through ``_affine_conv_core``)
    and the float32 composition on the same bfloat16 z and w: the value
    and the gradients wrt z, a, c, w."""
    z, a, c, wt = _fwd_inputs(rng, n, h, w, cin, cout)
    zb, wb = z.astype(jnp.bfloat16), wt.astype(jnp.bfloat16)
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))
    fused = lambda z, a, c, w: pallas_conv._affine_conv_core(
        z, a, c, w, True).astype(jnp.float32)
    comp = lambda z, a, c, w: _fwd_reference(*_f32(z, a, c, w))
    out = []
    for fn in (fused, comp):
        y, vjp = jax.vjp(fn, zb, a, c, wb)
        out.append((y, *vjp(cot)))
    return out


@pytest.mark.parametrize("shape", BF16_SHAPES)
def test_fwd_kernels_in_bf16_match_the_composition(rng, shape):
    got, want = _fwd_pair_grads(rng, *shape)
    for name, g, r in zip(["y", "dz", "da", "dc", "dw"], got, want):
        assert _worst(g, r) <= BF16_TOL, name


@pytest.mark.parametrize("shape", BF16_SHAPES)
def test_dx_kernel_in_bf16_matches_the_composition(rng, shape):
    """conv_bn_dx (``_conv_bn_core``'s backward) on bfloat16 x and w
    against the float32 conv + batch norm on the same values.  The conv
    bias's gradient is analytically 0 and compared at float32 above."""
    n, h, w, cin, cout = shape
    x, wt, cb, scale, bias, rm, rv = _inputs(rng, n, h, w, cin, cout)
    xb, wb = x.astype(jnp.bfloat16), wt.astype(jnp.bfloat16)
    cot = jnp.asarray(rng.randn(n, h, w, cout).astype(np.float32))
    fused = lambda x, w, s, b: pallas_conv._conv_bn_core(
        x, w, cb, s, b, EPS).astype(jnp.float32)
    comp = lambda x, w, s, b: _reference(*_f32(x, w), cb, s, b, rm, rv)[0]
    res = []
    for fn in (fused, comp):
        y, vjp = jax.vjp(fn, xb, wb, scale, bias)
        res.append((y, *vjp(cot)))
    for name, g, r in zip(["y", "dx", "dw", "dscale", "dbias"], *res):
        assert _worst(g, r) <= BF16_TOL, name


def _tile_told(kernel, h, w, cin, cout):
    """(nb, k, rows) the last call at these shapes told ``conv_bn_tile``."""
    from paddle_tpu import observe

    for smp in observe.REGISTRY.find("conv_bn_tile").samples():
        lab = smp["labels"]
        if (lab["kernel"], lab["h"], lab["w"], lab["cin"], lab["cout"]) \
                == (kernel, str(h), str(w), str(cin), str(cout)):
            return int(smp["value"]), lab["k"], int(lab["rows"])
    raise AssertionError(f"no conv_bn_tile for {kernel}")


@pytest.mark.parametrize("n,dot_rows,tile", [
    (6, 192, (3, "9c", 8)),    # 3 images a step of 6: 192 rows a product
    (7, 192, (1, "9c", 8)),    # 7 has no divisor between 1 and 3
    (2, 32, (1, "9c", 4)),     # a map over the rows: two bands of 4
])
def test_images_a_step_and_bands_match_the_composition(
        rng, monkeypatch, n, dot_rows, tile):
    """The rule's images a grid step (dA/dC summed over steps of several
    images) and bands of map rows, at an 8×8 map of 64 channels (64 rows
    an image at a pitch of 8), the rule's row target scaled down to it."""
    monkeypatch.setattr(pallas_conv, "_DOT_ROWS", dot_rows)
    got, want = _fwd_pair_grads(rng, n, 8, 8, 64, 64)
    for name, g, r in zip(["y", "dz", "da", "dc", "dw"], got, want):
        assert _worst(g, r) <= BF16_TOL, name
    from paddle_tpu.ops import kernels as K

    for kernel in (K.CONV_BN_FWD, K.CONV_BN_FWD_BWD):
        assert _tile_told(kernel, 8, 8, 64, 64) == tile, kernel


#: ResNet-50's four 3×3 stage shapes at the cell's batch, bfloat16: the
#: tile each fused kernel takes (forward: z in, y out; backward-data:
#: dy, z in, dz, x out), by ``_conv_tile``
STAGE_TILES = {
    (56, 64): (1, "9c", 8),     # 8 rows of 56 (at a pitch of 56): 448
    (28, 128): (1, "c", 14),    # two bands of 14 rows of 32: 448
    (14, 256): (2, "c", 14),    # two images of 14 × 16: 448
    (7, 512): (8, "c", 7),      # eight images of 7 × 8: 448
}


@pytest.mark.parametrize("hw,ch", sorted(STAGE_TILES))
def test_the_tile_rule_at_resnet50s_stage_shapes(hw, ch):
    img = hw * pallas_conv._rup(hw, 16) * pallas_conv._lanes(ch) * 2
    for streams in (2, 4):          # forward, backward-data
        t = pallas_conv._conv_tile(hw, hw, ch, ch, 128, 2, streams * img)
        assert (t.nb, t.k, t.rows) == STAGE_TILES[hw, ch], streams


def test_the_tile_rule_keeps_to_a_smaller_chips_vmem(monkeypatch):
    """On a chip of 32 MiB of VMEM the kernels ask Mosaic for 16 MiB and
    a grid step holds at most 12: a 64-channel kernel in bfloat16 takes
    bands of half the rows it takes on a v5e; the gate, which asks for a
    tile at float32 operands, passes the 128-channel pair and sends the
    512-channel one, whose weights alone take 9.4 MB in two buffers, to
    the unfused composition."""
    class Info:
        vmem_capacity_bytes = 32 << 20

    monkeypatch.setattr(pallas_conv, "is_tpu", lambda: True)
    monkeypatch.setattr(pallas_conv.pltpu, "get_tpu_info", lambda: Info)
    assert pallas_conv._vmem_limit() == 16 << 20
    img = 4 * 56 * 64 * 128 * 2         # backward-data: four streams
    t = pallas_conv._conv_tile(56, 56, 64, 64, 128, 2, img)
    assert (t.nb, t.k, t.rows) == (1, "9c", 4)
    assert pallas_conv._tile_bytes(t, 56, 56, 64, 64, 2, img) <= 12 << 20
    assert pallas_conv.fused_fwd_ok(28, 28, 128, 128)
    assert not pallas_conv.fused_fwd_ok(7, 7, 512, 512)
