"""Plain reference of ResNet-50 (He et al. 2015, arXiv:1512.03385,
table 1; the original v1 bottleneck with the stride on the first 1x1
convolution, as the source paper's model zoo builds it) under softmax
cross-entropy.  Straightforward ``jax.numpy``/``lax`` in float32; the
caller sets ``jax.default_matmul_precision("highest")``.  No kernels,
no fusion; each bottleneck is rematerialised so that the backward pass
of a batch of 128 fits beside nothing else on one chip.

``cast`` rounds the operands of every convolution and of the classifier
matmul; the identity for the reference, a lower precision for the
control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (3, 4, 6, 3)
BN_EPS = 1e-5
CE_EPS = 1e-8          # the cost is -log(clip(p, eps, 1)), as in the source


def conv_plan(sizes):
    """(name, k, cin, cout, stride, pad) of every conv, in the order a
    reader of the paper would list them: stem; per block shortcut (where
    the shape changes), then the three convs."""
    plan = [("stem", 7, 3, 64, 2, 3)]
    cin = 64
    for si, blocks in enumerate(STAGES):
        ch = 64 * 2 ** si
        for bi in range(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            p = f"s{si + 1}b{bi + 1}"
            if cin != ch * 4 or stride != 1:
                plan.append((p + ".sc", 1, cin, ch * 4, stride, 0))
            plan.append((p + ".c1", 1, cin, ch, stride, 0))
            plan.append((p + ".c2", 3, ch, ch, 1, 1))
            plan.append((p + ".c3", 1, ch, ch * 4, 1, 0))
            cin = ch * 4
    return plan


def param_spec(sizes):
    spec = {}
    for name, k, cin, cout, _, _ in conv_plan(sizes):
        spec[name + ".w"] = ((k, k, cin, cout), "normal",
                             math.sqrt(2.0 / (k * k * cin)))
        spec[name + ".b"] = ((cout,), "zeros", 0.0)
        spec[name + ".g"] = ((cout,), "gain", 0.1)
        spec[name + ".beta"] = ((cout,), "normal", 0.1)
    n_cls = int(sizes["num_classes"])
    spec["fc.w"] = ((2048, n_cls), "normal", 1.0 / math.sqrt(2048.0))
    spec["fc.b"] = ((n_cls,), "zeros", 0.0)
    return spec


def _conv_bn(x, p, name, stride, pad, relu, cast):
    y = lax.conv_general_dilated(
        cast(x), cast(p[name + ".w"]), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y + p[name + ".b"]
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = (y - mean) * lax.rsqrt(var + BN_EPS) * p[name + ".g"] \
        + p[name + ".beta"]
    return jax.nn.relu(y) if relu else y


def _bottleneck(x, p, prefix, stride, has_sc, cast):
    short = _conv_bn(x, p, prefix + ".sc", stride, 0, False, cast) \
        if has_sc else x
    y = _conv_bn(x, p, prefix + ".c1", stride, 0, True, cast)
    y = _conv_bn(y, p, prefix + ".c2", 1, 1, True, cast)
    y = _conv_bn(y, p, prefix + ".c3", 1, 0, False, cast)
    return jax.nn.relu(short + y)


def loss(params, feed, sizes, cast=lambda a: a):
    """Mean softmax cross-entropy of one batch.  ``feed['image']`` is
    [B, 3*H*W] rows in channel-major order, ``feed['label']`` [B]."""
    px = int(sizes["image_size"])
    b = feed["image"].shape[0]
    x = jnp.transpose(feed["image"].reshape(b, 3, px, px), (0, 2, 3, 1))
    x = _conv_bn(x, params, "stem", 2, 3, True, cast)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    cin = 64
    for si, blocks in enumerate(STAGES):
        ch = 64 * 2 ** si
        for bi in range(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            prefix = f"s{si + 1}b{bi + 1}"
            has_sc = cin != ch * 4 or stride != 1
            keys = [k for k in params if k.startswith(prefix + ".")]
            block = jax.checkpoint(
                lambda x, bp, prefix=prefix, stride=stride, has_sc=has_sc:
                _bottleneck(x, bp, prefix, stride, has_sc, cast))
            x = block(x, {k: params[k] for k in keys})
            cin = ch * 4
    x = jnp.mean(x, axis=(1, 2))                       # 7x7 average pool
    logits = cast(x) @ cast(params["fc.w"]) + params["fc.b"]
    prob = jax.nn.softmax(logits, axis=-1)
    logp = jnp.log(jnp.clip(prob, CE_EPS, 1.0))
    picked = jnp.take_along_axis(
        logp, feed["label"].reshape(-1, 1).astype(jnp.int32), axis=-1)
    return -jnp.mean(picked)
