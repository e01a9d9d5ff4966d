"""Neural-network ops: conv, pooling, normalization, dropout.

Replaces the reference's cuDNN wrappers (``hl_cuda_cudnn.cc``), the im2col
GEMM conv path (``paddle/function/GemmConvOp``, ``paddle/operators/math/
im2col``), pooling (``hl_cnn``/``pool_op``), batch_norm
(``paddle/operators/batch_norm_op.cc``, ``CudnnBatchNormLayer``), LRN
(``CrossMapNormLayer``/``lrn_op``), dropout, maxout, bilinear interp, prelu.

TPU-first choices: native ``lax.conv_general_dilated`` (XLA maps convs onto
the MXU directly — no im2col materialization), **NHWC layout** (channels on
the 128-lane minor dimension), bf16 compute via the precision policy.  The
reference's NCHW configs are converted at the layer-engine boundary.
"""

from __future__ import annotations

from functools import partial as _partial

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.device import batch_local, kernel_devices
from ..core.dtypes import current_policy, record_op_precision
from ..observe import counter
from .registry import register_op

IntOr2 = Union[int, Tuple[int, int]]


def _record_conv_dispatch(op: str, path: str, reason: str = "") -> None:
    """One lowering decision of the fused conv/BN family (trace-time:
    ticks once per compiled program per shape — see the RNN counter in
    ops/recurrent_ops.py for the convention)."""
    counter(
        "conv_dispatch_total",
        "conv+BN lowering decisions by tier (trace-time; reason set "
        "when a fusable-looking call took the unfused composition)",
    ).inc(op=op, path=path, reason=reason)


def _pair(v: IntOr2) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _stem_space_to_depth(x, w, dn_format="NHWC"):
    """Exact reformulation of the 7×7/stride-2/pad-3 stem conv as a
    4×4/stride-1 conv over 2×2 space-to-depth blocks (the MLPerf conv0
    optimization): with C=3 the MXU's 128-deep contraction is ~2% busy;
    at 4C=12 the filter-gradient conv in particular stops being the
    slowest kernel of the step.  Derivation: output row i reads input
    rows 2i−3…2i+3 = block-rows i−2…i+1 → kernel 4, pad (2,1); kernel
    entry (pu,a) holds W[2pu+a−1] (u=−1,7 fall off → zero-pad W to 8².
    Same weights/checkpoint layout — the transform is per-step and XLA
    constant-folds it outside the loop."""
    n, h, w_, c = x.shape
    x2 = x.reshape(n, h // 2, 2, w_ // 2, 2, c) \
        .transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w_ // 2, 4 * c)
    kh, kw, ci, co = w.shape
    w8 = jnp.zeros((8, 8, ci, co), w.dtype).at[1:8, 1:8].set(w)
    w2 = w8.reshape(4, 2, 4, 2, ci, co).transpose(0, 2, 1, 3, 4, 5) \
        .reshape(4, 4, 4 * ci, co)
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape,
                                    (dn_format, "HWIO", dn_format))
    return lax.conv_general_dilated(
        x2, w2, (1, 1), [(2, 1), (2, 1)], dimension_numbers=dn)


@register_op("conv2d")
def conv2d(x, w, stride: IntOr2 = 1, padding="SAME", dilation: IntOr2 = 1,
           groups: int = 1, data_format: str = "NHWC"):
    """2-D convolution.

    x: [N,H,W,C] (NHWC) or [N,C,H,W]; w: [KH,KW,Cin/groups,Cout] (HWIO).
    Reference: ``ExpandConvLayer``/``conv2d op`` — those im2col+GEMM; XLA
    lowers this directly to MXU convolutions.
    """
    pol = current_policy()
    record_op_precision("conv2d")
    x = x.astype(pol.compute_dtype)
    w = w.astype(pol.compute_dtype)
    if isinstance(padding, int):
        padding = [(padding, padding)] * 2
    elif isinstance(padding, (tuple, list)) and isinstance(padding[0], int):
        padding = [(padding[0], padding[0]), (padding[1], padding[1])]
    if (data_format == "NHWC" and groups == 1 and x.ndim == 4
            and w.shape[:2] == (7, 7) and w.shape[2] <= 4
            and _pair(stride) == (2, 2) and _pair(dilation) == (1, 1)
            and padding == [(3, 3), (3, 3)]
            and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0):
        return _stem_space_to_depth(x, w).astype(pol.output_dtype)
    if (data_format == "NHWC" and groups == 1 and x.ndim == 4
            and w.shape[:2] == (1, 1) and _pair(stride) == (1, 1)
            and _pair(dilation) == (1, 1)
            and padding in ("SAME", "VALID", [(0, 0), (0, 0)])):
        # A 1×1 stride-1 conv IS a matmul over the flattened spatial
        # dims; stating it as dot_general gives XLA the plain-GEMM
        # layout space instead of the convolution lowering (half of
        # ResNet-50's convs take this path; measured 2698 → 3065
        # samples/s on the train step).  Stride-2 1×1 was tried as
        # subsample-then-matmul and measured 25% WORSE (the strided
        # slice's backward is a scatter) — those stay on lax.conv.
        n, h, ww, cin = x.shape
        out = (x.reshape(n * h * ww, cin) @ w.reshape(cin, w.shape[3]))
        return out.reshape(n, h, ww, -1).astype(pol.output_dtype)
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape,
        (data_format, "HWIO", data_format))
    # No preferred_element_type here: conv's transpose (grad) rule can't
    # mix a fp32 cotangent with bf16 operands in current jax; the MXU
    # accumulates in fp32 natively, so cast-after is equivalent.
    out = lax.conv_general_dilated(
        x, w, window_strides=_pair(stride), padding=padding,
        rhs_dilation=_pair(dilation), dimension_numbers=dn,
        feature_group_count=groups)
    return out.astype(pol.output_dtype)


@register_op("conv2d_transpose")
def conv2d_transpose(x, w, stride: IntOr2 = 1, padding="SAME",
                     data_format: str = "NHWC"):
    """Transposed conv (``conv2d_transpose_op.cc``). w: [KH,KW,Cout,Cin].

    Explicit padding follows the reference size contract
    out = (i−1)·s + k − 2p, implemented as the scatter-conv identity:
    conv of the stride-dilated input with the spatially-flipped filter
    at padding k−1−p.  (``lax.conv_transpose`` with explicit padding
    center-crops instead — wrong sizes for s > 1.)  String paddings keep
    the lax fast path.
    """
    pol = current_policy()
    x = x.astype(pol.compute_dtype)
    w = w.astype(pol.compute_dtype)
    if isinstance(padding, str):
        out = lax.conv_transpose(
            x, w, strides=_pair(stride), padding=padding,
            dimension_numbers=(data_format, "HWIO", data_format),
            transpose_kernel=True)
        return out.astype(pol.output_dtype)
    if isinstance(padding, int):
        padding = [(padding, padding)] * 2
    kh, kw = w.shape[0], w.shape[1]
    # HWIO with I = Cin (matching x's channels), spatially flipped
    w_flip = jnp.transpose(w, (0, 1, 3, 2))[::-1, ::-1]
    dn = lax.conv_dimension_numbers(x.shape, w_flip.shape,
                                    (data_format, "HWIO", data_format))
    out = lax.conv_general_dilated(
        x, w_flip, window_strides=(1, 1),
        padding=[(kh - 1 - padding[0][0], kh - 1 - padding[0][1]),
                 (kw - 1 - padding[1][0], kw - 1 - padding[1][1])],
        lhs_dilation=_pair(stride), dimension_numbers=dn)
    return out.astype(pol.output_dtype)


@register_op("conv3d")
def conv3d(x, w, stride=1, padding="SAME", data_format: str = "NDHWC"):
    """3-D convolution (``Conv3DLayer``). x: [N,D,H,W,C]; w: [KD,KH,KW,I,O]."""
    pol = current_policy()
    x = x.astype(pol.compute_dtype)
    w = w.astype(pol.compute_dtype)
    s = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        padding = [(padding, padding)] * 3
    dn = lax.conv_dimension_numbers(x.shape, w.shape, (data_format, "DHWIO", data_format))
    return lax.conv_general_dilated(
        x, w, window_strides=s, padding=padding,
        dimension_numbers=dn).astype(pol.output_dtype)


@register_op("conv3d_transpose")
def conv3d_transpose(x, w, stride=1, padding="SAME",
                     data_format: str = "NDHWC"):
    """Transposed 3-D conv (``DeConv3DLayer``). x: [N,D,H,W,C];
    w: [KD,KH,KW,Cout,Cin] (transpose_kernel layout, like conv2d_transpose)."""
    pol = current_policy()
    x = x.astype(pol.compute_dtype)
    w = w.astype(pol.compute_dtype)
    s = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        padding = [(padding, padding)] * 3
    out = lax.conv_transpose(
        x, w, strides=s, padding=padding,
        dimension_numbers=(data_format, "DHWIO", data_format),
        transpose_kernel=True)
    return out.astype(pol.output_dtype)


@register_op("pool3d")
def pool3d(x, pool_type: str = "max", window=2, stride=2, padding=0):
    """3-D max/avg pool over NDHWC (``Pool3DLayer``); avg excludes padding
    from the divisor like ``_pool``."""
    kd, kh, kw = (window,) * 3 if isinstance(window, int) else tuple(window)
    sd, sh, sw = (stride,) * 3 if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        padding = (padding,) * 3
    pd, ph, pw = padding
    dims, strides = (1, kd, kh, kw, 1), (1, sd, sh, sw, 1)
    pads = [(0, 0), (pd, pd), (ph, ph), (pw, pw), (0, 0)]
    if "max" in pool_type:
        return lax.reduce_window(x, -np.inf, lax.max, dims, strides, pads)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, dims,
                               strides, pads)
    return summed / counts


def _pool(x, kind: str, window: IntOr2, stride: IntOr2, padding,
          data_format: str = "NHWC"):
    kh, kw = _pair(window)
    sh, sw = _pair(stride)
    if data_format == "NHWC":
        dims, strides = (1, kh, kw, 1), (1, sh, sw, 1)
        spatial = [1, 2]
    else:  # NCHW
        dims, strides = (1, 1, kh, kw), (1, 1, sh, sw)
        spatial = [2, 3]
    if isinstance(padding, int):
        pads = [(0, 0)] * 4
        for ax in spatial:
            pads[ax] = (padding, padding)
    elif isinstance(padding, str):
        pads = padding
    else:
        pads = [(0, 0)] * 4
        for ax, p in zip(spatial, padding):
            pads[ax] = _pair(p)
    # init values MUST be python scalars: a device-array init becomes a
    # tracer under jit and jax then can't pattern-match the max/add monoid,
    # leaving a generic reduce_window with no autodiff rule.
    if kind == "max":
        dt = np.dtype(x.dtype)
        # branch on integer (not floating): bf16/fp8 are numpy void types
        init = np.iinfo(dt).min if np.issubdtype(dt, np.integer) \
            else -np.inf
        return lax.reduce_window(x, init, lax.max, dims, strides, pads)
    # avg: exclude padding from the divisor (cuDNN
    # CUDNN_POOLING_AVERAGE_COUNT_EXCLUDE_PADDING — reference default).
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
    ones = jnp.ones_like(x)
    counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
    return summed / counts


@register_op("pool2d")
def pool2d(x, pool_type: str = "max", window: IntOr2 = 2, stride: IntOr2 = 2,
           padding=0, data_format: str = "NHWC", global_pooling: bool = False):
    if global_pooling:
        axes = (1, 2) if data_format == "NHWC" else (2, 3)
        red = jnp.max if pool_type == "max" else jnp.mean
        return red(x, axis=axes, keepdims=True)
    return _pool(x, pool_type, window, stride, padding, data_format)


@register_op("max_pool2d_with_index", n_outputs=2)
def max_pool2d_with_index(x, window: IntOr2 = 2, stride: IntOr2 = 2,
                          padding: int = 0):
    """Max pool returning flat spatial argmax indices
    (``pool_with_index_op``), NHWC."""
    n, h, w, c = x.shape
    kh, kw = _pair(window)
    sh, sw = _pair(stride)
    pos = jnp.arange(h * w, dtype=jnp.float32).reshape(1, h, w, 1)
    pos = jnp.broadcast_to(pos, x.shape)

    def select(acc, cur):
        av, ai = acc
        cv, ci = cur
        take = cv > av
        return jnp.where(take, cv, av), jnp.where(take, ci, ai)

    pads = [(0, 0), (padding, padding), (padding, padding), (0, 0)]
    (vals, idxs) = lax.reduce_window(
        (x, pos),
        (jnp.asarray(-jnp.inf, x.dtype), jnp.asarray(-1.0)),
        select, (1, kh, kw, 1), (1, sh, sw, 1), pads)
    return vals, idxs.astype(jnp.int32)


@register_op("spp")
def spatial_pyramid_pool(x, pyramid_height: int, pool_type: str = "max"):
    """Spatial pyramid pooling (``SpatialPyramidPoolLayer``), NHWC → [N, F]."""
    n, h, w, c = x.shape
    outs = []
    for lvl in range(pyramid_height):
        bins = 2 ** lvl
        # adaptive pooling: split H/W into `bins` regions
        hs = [h * i // bins for i in range(bins + 1)]
        ws = [w * i // bins for i in range(bins + 1)]
        for i in range(bins):
            for j in range(bins):
                region = x[:, hs[i]:hs[i + 1], ws[j]:ws[j + 1], :]
                red = jnp.max if pool_type == "max" else jnp.mean
                outs.append(red(region, axis=(1, 2)))
    return jnp.concatenate(outs, axis=-1).reshape(n, -1)


def _bn_axes(ndim: int, data_format: str) -> Tuple[Tuple[int, ...], int]:
    c_ax = ndim - 1 if data_format.endswith("C") else 1
    return tuple(i for i in range(ndim) if i != c_ax), c_ax


def _bn_apply(x, scale, bias, m, inv, c_ax):
    """One fused multiply-add pass in x's dtype with the per-channel
    scale/offset folded."""
    shape = [1] * x.ndim
    shape[c_ax] = x.shape[c_ax]
    a = (inv * scale).astype(x.dtype).reshape(shape)
    b = (bias - m * inv * scale).astype(x.dtype).reshape(shape)
    return x * a + b


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train(x, scale, bias, eps, axes, c_ax):
    (y, _stats), _res = _bn_train_fwd(x, scale, bias, eps, axes, c_ax)
    return y


def _bn_stats(x, axes):
    m = jnp.mean(x, axis=axes, dtype=jnp.float32)
    # square in fp32: the upcast happens in-register on the same bf16
    # read, and a bf16 x*x loses all low bits when |mean| >> std,
    # collapsing the E[x²]−E[x]² difference to 0
    m2 = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axes)
    v = jnp.maximum(m2 - m * m, 0.0)
    return m, v


def _bn_train_fwd(x, scale, bias, eps, axes, c_ax):
    m, v = _bn_stats(x, axes)
    inv = lax.rsqrt(v + eps)
    y = _bn_apply(x, scale, bias, m, inv, c_ax)
    return (y, (m, v)), (x, scale, m, inv)


def _bn_train_bwd(eps, axes, c_ax, res, cts):
    """Hand-fused BN backward (the cuDNN ``BatchNormBackward`` formula):

        dbias  = Σ dy
        dscale = Σ dy·x̂
        dx     = scale·inv · (dy − dbias/N − x̂·dscale/N)

    ONE fused reduction pass over (dy, x) for both sums + one apply pass
    — autodiff through the E[x²] stats path emits twice the reduction
    traffic, which profiling showed as ~18% of the ResNet train step
    ("convert_reduce" loop fusions).  Stats cotangents (running-average
    buffers) are dropped: buffers are side-channel state with
    stop-gradient semantics, as in the reference
    (``BatchNormalizationLayer`` never backprops moving averages).
    """
    dy, _ = cts
    x, scale, m, inv = res
    shape = [1] * x.ndim
    shape[c_ax] = x.shape[c_ax]
    n = np.prod([x.shape[i] for i in axes]).astype(np.float32)
    xhat_f = (x.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
    dy_f = dy.astype(jnp.float32)
    dbias = jnp.sum(dy_f, axis=axes)
    dscale = jnp.sum(dy_f * xhat_f, axis=axes)
    coeff = (scale * inv).astype(jnp.float32).reshape(shape)
    dx = coeff * (dy_f - (dbias / n).reshape(shape)
                  - xhat_f * (dscale / n).reshape(shape))
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(scale.dtype))


def _bn_train_y_fwd(x, scale, bias, eps, axes, c_ax):
    (y, _stats), res = _bn_train_fwd(x, scale, bias, eps, axes, c_ax)
    return y, res


def _bn_train_y_bwd(eps, axes, c_ax, res, dy):
    return _bn_train_bwd(eps, axes, c_ax, res, (dy, None))


_bn_train.defvjp(_bn_train_y_fwd, _bn_train_y_bwd)


@register_op("batch_norm", n_outputs=3)
def batch_norm(x, scale, bias, running_mean, running_var,
               momentum: float = 0.9, eps: float = 1e-5,
               is_training: bool = True, data_format: str = "NHWC"):
    """Batch normalization (``batch_norm_op.cc``, ``BatchNormalizationLayer``).

    Returns (y, new_running_mean, new_running_var).  Stats accumulate in
    fp32 regardless of compute dtype (TPU numerics), but the tensor is
    READ in its own dtype (one pass, E[x²]−E[x]² with fp32 accumulators)
    and the normalization is a single multiply-add in x's dtype with the
    per-channel scale/offset folded — under bf16 activations this halves
    BN's HBM traffic, which dominates ResNet-class steps.  Training mode
    uses a hand-fused custom-VJP backward (see :func:`_bn_train_bwd`).
    """
    axes, c_ax = _bn_axes(x.ndim, data_format)
    if is_training:
        # stats recomputed outside the custom_vjp for the running
        # averages (cheap per-channel math; XLA CSEs the reduction with
        # the one inside _bn_train's forward)
        m, v = _bn_stats(x, axes)
        y = _bn_train(x, scale, bias, eps, axes, c_ax)
        new_rm = momentum * running_mean + (1 - momentum) * m
        new_rv = momentum * running_var + (1 - momentum) * v
        return y, new_rm, new_rv
    inv = lax.rsqrt(running_var + eps)
    y = _bn_apply(x, scale, bias, running_mean, inv, c_ax)
    return y, running_mean, running_var


def _gemm_prologue_ok(x_shape, w_shape, stride, padding, dilation,
                      groups, data_format) -> bool:
    """Static gate for the 1×1 GEMM-prologue path of
    :func:`affine_act_conv2d`: the same family the plain-GEMM ``conv2d``
    fast path accepts (1×1 stride-1 NHWC, groups=1, zero pad)."""
    if data_format != "NHWC" or groups != 1:
        return False
    if len(x_shape) != 4 or len(w_shape) != 4 \
            or tuple(w_shape[:2]) != (1, 1):
        return False
    if _pair(stride) != (1, 1) or _pair(dilation) != (1, 1):
        return False
    if isinstance(padding, str):
        return padding in ("SAME", "VALID")
    if isinstance(padding, int):
        return padding == 0
    pads = [_pair(p) for p in padding]
    return pads == [(0, 0), (0, 0)]


def _affine_apply(z, a, c, act: str):
    """The unfused BN-apply formula: act(a·z + c) in z's dtype — the
    exact composition the fused paths replace (and fall back to)."""
    x = z * a.astype(z.dtype) + c.astype(z.dtype)
    if act == "relu":
        return jax.nn.relu(x)
    if act in ("", "linear"):
        return x
    from . import get_activation

    return get_activation(act)(x)


@_partial(jax.custom_vjp, nondiff_argnums=(4,))
def _affine_conv1x1_core(z, a, c, w, relu):
    """act(a·z + c) @ w — the 1×1 stride-1 GEMM conv with the upstream
    BN's folded affine (+ReLU) as a fused prologue.  Stating the
    elementwise prologue inline hands XLA the GEMM operand to fuse it
    into, and the custom backward recomputes x from the raw z residual
    instead of saving the normalized activation — the same recompute
    discipline as the Pallas 3×3 path."""
    return _affine_1x1_fwd(z, a, c, w, relu)[0]


def _affine_1x1_fwd(z, a, c, w, relu):
    n, h, ww, cin = z.shape
    x = _affine_apply(z, a, c, "relu" if relu else "")
    out = (x.reshape(n * h * ww, cin) @ w.reshape(cin, -1)) \
        .reshape(n, h, ww, -1)
    return out, (z, a, c, w)


def _affine_1x1_bwd(relu, res, dy):
    z, a, c, w = res
    n, h, ww, cin = z.shape
    cout = w.shape[3]
    # mask/x recomputed from z exactly as the forward formed them
    u = z * a.astype(z.dtype) + c.astype(z.dtype)
    x = jax.nn.relu(u) if relu else u
    t = (dy.reshape(n * h * ww, cout) @ w.reshape(cin, cout).T) \
        .reshape(z.shape).astype(jnp.float32)
    du = jnp.where(u > 0, t, 0.0) if relu else t
    dz = (a * du).astype(z.dtype)
    da = jnp.sum(z.astype(jnp.float32) * du, axis=(0, 1, 2))
    dc = jnp.sum(du, axis=(0, 1, 2))
    dw = jax.lax.dot_general(
        x.reshape(n * h * ww, cin), dy.reshape(n * h * ww, cout),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(w.shape)
    return dz, da.astype(a.dtype), dc.astype(c.dtype), dw.astype(w.dtype)


_affine_conv1x1_core.defvjp(_affine_1x1_fwd, _affine_1x1_bwd)


@register_op("affine_act_conv2d")
def affine_act_conv2d(z, a, c, w, conv_bias=None, act: str = "relu",
                      is_training: bool = True, stride: IntOr2 = 1,
                      padding="SAME", dilation: IntOr2 = 1,
                      groups: int = 1, data_format: str = "NHWC"):
    """Fused BN-affine(+act)→conv forward: y = conv(act(a·z + c), w).

    The forward half of the fused conv/BN family: ``a``/``c`` are the
    upstream batch-norm's folded per-channel scale/offset (train-mode
    batch stats or eval-mode running stats — folded identically), and
    the normalized activation never materializes in HBM.  Dispatch:

    - 3×3 stride-1 pad-1 NHWC with 64-multiple channels → the Pallas
      forward kernel (:mod:`paddle_tpu.ops.pallas_conv`), the affine
      applied in its VMEM input pipeline;
    - 1×1 stride-1 NHWC → the plain-GEMM conv path with the affine as
      a fused GEMM prologue (custom backward, raw-z residuals);
    - anything else — eval mode, off-tile channels, stride-2, other
      activations — the exact unfused composition.

    Gradients flow into z, a, c, and w; the caller owns the BN-side
    chain rule from (a, c) back to scale/bias and the batch stats.
    """
    from . import pallas_conv

    pol = current_policy()
    record_op_precision("affine_act_conv2d")
    relu = act == "relu"
    zs, ws = jnp.shape(z), jnp.shape(w)
    fusable_act = act in ("relu", "", "linear")
    if is_training and fusable_act and pallas_conv.fusable_fwd(
            zs, ws, stride, padding, dilation, groups, data_format):
        _record_conv_dispatch("affine_act_conv2d", "pallas3x3")
        # whole images a grid step: batch-local (the dA/dC/dW sums over
        # the batch come back through the replicated-input transpose)
        out = batch_local(
            lambda z_, a_, c_, w_: pallas_conv._affine_conv_core(
                z_, a_, c_, w_, relu),
            (z.astype(pol.compute_dtype), a.astype(jnp.float32),
             c.astype(jnp.float32), w.astype(pol.compute_dtype)),
            batch_in=(True, False, False, False), batch_out=True)
        out = out.astype(pol.output_dtype)
    elif is_training and fusable_act and _gemm_prologue_ok(
            zs, ws, stride, padding, dilation, groups, data_format):
        _record_conv_dispatch("affine_act_conv2d", "gemm1x1")
        out = _affine_conv1x1_core(
            z.astype(pol.compute_dtype), a.astype(jnp.float32),
            c.astype(jnp.float32), w.astype(pol.compute_dtype), relu)
        out = out.astype(pol.output_dtype)
    else:
        _record_conv_dispatch(
            "affine_act_conv2d", "unfused",
            "eval mode" if not is_training
            else "non-fusable activation" if not fusable_act
            else "off-tile shape/stride/layout")
        out = conv2d(_affine_apply(z, a, c, act), w, stride=stride,
                     padding=padding, dilation=dilation, groups=groups,
                     data_format=data_format)
    if conv_bias is not None:
        out = out + conv_bias
    return out


def bn_folded_affine(x, scale, bias, running_mean, running_var,
                     momentum: float = 0.9, eps: float = 1e-5,
                     is_training: bool = True, data_format: str = "NHWC"):
    """The folded per-channel affine of :func:`batch_norm` WITHOUT
    applying it, plus the running-stat update: returns
    ``(a, c, new_rm, new_rv)`` with ``batch_norm(x, ...) ==
    act(a·x + c)`` elementwise.  This is the deferred form consumed by
    :func:`affine_act_conv2d` (forward conv+BN fusion); keeping it next
    to ``batch_norm`` pins both paths to the same stats/eps/momentum
    conventions."""
    axes, _c_ax = _bn_axes(x.ndim, data_format)
    if is_training:
        m, v = _bn_stats(x, axes)
        new_rm = momentum * running_mean + (1 - momentum) * m
        new_rv = momentum * running_var + (1 - momentum) * v
    else:
        m, v = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    inv = lax.rsqrt(v + eps)
    a = (scale * inv).astype(jnp.float32)
    c = (bias - m * a).astype(jnp.float32)
    return a, c, new_rm, new_rv


@register_op("conv2d_bn", n_outputs=3)
def conv2d_bn(x, w, conv_bias, scale, bias, running_mean, running_var,
              momentum: float = 0.9, eps: float = 1e-5,
              is_training: bool = True, stride: IntOr2 = 1,
              padding="SAME", dilation: IntOr2 = 1, groups: int = 1,
              data_format: str = "NHWC", in_affine=None):
    """Fused conv + batch-norm (training): same contract as
    ``conv2d`` (+ optional conv bias) followed by ``batch_norm``, but
    for the 3×3 stride-1 NHWC family the backward runs through the
    Pallas backward-data kernel in :mod:`paddle_tpu.ops.pallas_conv`,
    which applies the BN-backward per-channel affine while streaming
    tiles through VMEM — the dz apply pass and its HBM round-trip
    disappear (the cuDNN fused conv/BN backward of
    ``hl_cuda_cudnn.cc``, rebuilt for TPU).  Shapes outside the fused
    family, eval mode, and non-NHWC layouts take the exact unfused
    composition — same results either way, pinned by
    ``tests/test_pallas_conv.py``.

    ``in_affine=(a, c, act)`` composes the FORWARD fusion into the same
    pair: ``x`` is then the upstream BN's raw input z and the pair
    computes BN(conv(act(a·z + c)) + cb) with the prologue streamed
    through the Pallas kernels' input pipelines in both directions
    (``pallas_conv._chain_core``).  Off-family shapes materialize the
    affine exactly (the unfused BN apply) and continue as a plain pair.

    Returns (y, new_running_mean, new_running_var) like ``batch_norm``.
    """
    from . import pallas_conv

    pol = current_policy()
    record_op_precision("conv2d_bn")
    # the fused pair computes the BatchNorm statistics INSIDE its
    # custom_vjp core, over whatever rows it is handed — not batch-local,
    # so under a multi-device mesh (where Mosaic must be shard_mapped,
    # core/device.batch_local) the pair takes the XLA composition, whose
    # statistics GSPMD reduces across the devices
    on_mesh = kernel_devices() > 1
    if in_affine is not None:
        a1, c1, act1 = in_affine
        xs, ws = jnp.shape(x), jnp.shape(w)
        if (is_training and not on_mesh
                and act1 in ("relu", "", "linear")
                and pallas_conv.fusable(xs, ws, stride, padding,
                                        dilation, groups, data_format)
                and pallas_conv.fused_chain_ok(
                    xs[1], xs[2], int(ws[2]), int(ws[3]))):
            _record_conv_dispatch("conv2d_bn", "chain")
            xc = x.astype(pol.compute_dtype)
            wc = w.astype(pol.compute_dtype)
            cb = jnp.zeros((wc.shape[3],), jnp.float32) \
                if conv_bias is None else conv_bias
            y, m, v = pallas_conv._chain_core(
                xc, a1.astype(jnp.float32), c1.astype(jnp.float32), wc,
                cb, scale, bias, eps, act1 == "relu")
            new_rm = momentum * running_mean + (1 - momentum) * m
            new_rv = momentum * running_var + (1 - momentum) * v
            return y.astype(pol.output_dtype), new_rm, new_rv
        # outside the chain family: materialize the affine exactly (the
        # unfused BN apply formula) and continue as a plain conv→BN pair
        x = _affine_apply(x, a1, c1, act1)
    if on_mesh or not (is_training and pallas_conv.fusable(
            jnp.shape(x), jnp.shape(w), stride, padding, dilation,
            groups, data_format)):
        _record_conv_dispatch(
            "conv2d_bn", "unfused",
            "eval mode" if not is_training
            else "multi-device mesh (BN statistics span the batch)"
            if on_mesh else "off-tile shape/stride/layout")
        z = conv2d(x, w, stride=stride, padding=padding,
                   dilation=dilation, groups=groups,
                   data_format=data_format)
        if conv_bias is not None:
            z = z + conv_bias
        return batch_norm(z, scale, bias, running_mean, running_var,
                          momentum=momentum, eps=eps,
                          is_training=is_training,
                          data_format=data_format)
    _record_conv_dispatch("conv2d_bn", "fused")
    xc = x.astype(pol.compute_dtype)
    wc = w.astype(pol.compute_dtype)
    cb = jnp.zeros((wc.shape[3],), jnp.float32) if conv_bias is None \
        else conv_bias
    y = pallas_conv._conv_bn_core(xc, wc, cb, scale, bias, eps)
    # stats recomputed outside the custom_vjp for the running averages
    # (XLA CSEs the conv and reductions with the ones inside the core)
    z = pallas_conv._conv3x3(xc, wc) + cb.astype(xc.dtype)
    m, v = _bn_stats(z, (0, 1, 2))
    new_rm = momentum * running_mean + (1 - momentum) * m
    new_rv = momentum * running_var + (1 - momentum) * v
    return y.astype(pol.output_dtype), new_rm, new_rv


@register_op("lrn")
def lrn(x, n: int = 5, k: float = 2.0, alpha: float = 1e-4, beta: float = 0.75):
    """Local response normalization across channels, NHWC
    (``lrn_op.cc``, ``CrossMapNormLayer`` — note gserver uses
    ``scale = k + alpha * sum``; op uses same form)."""
    sq = jnp.square(x)
    half = n // 2
    pads = [(0, 0)] * (x.ndim - 1) + [(half, half)]
    sq = jnp.pad(sq, pads)
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + lax.slice_in_dim(sq, i, i + x.shape[-1], axis=-1)
    return x / jnp.power(k + alpha * acc, beta)


@register_op("dropout")
def dropout(x, key, rate: float = 0.5, is_training: bool = True):
    if not is_training or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


@register_op("maxout")
def maxout(x, groups: int, data_format: str = "NHWC"):
    """Max over channel groups (``MaxOutLayer``/``hl_maxout``)."""
    if data_format == "NHWC":
        n, h, w, c = x.shape
        return jnp.max(x.reshape(n, h, w, c // groups, groups), axis=-1)
    n, c, h, w = x.shape
    return jnp.max(x.reshape(n, groups, c // groups, h, w), axis=1)


@register_op("prelu")
def prelu(x, alpha):
    return jnp.where(x >= 0, x, alpha * x)


@register_op("bilinear_interp")
def bilinear_interp(x, out_h: int, out_w: int):
    """Bilinear upsampling, NHWC (``BilinearInterpLayer``/``hl_bilinear``,
    align_corners-style ratio as the reference computes it)."""
    n, h, w, c = x.shape
    return jax.image.resize(x, (n, out_h, out_w, c), method="bilinear")


@register_op("feature_map_expand")
def feature_map_expand(x, num_filters: int, as_row: bool = True):
    """Tile a [B, D] input into [B, num_filters*D] (``FeatureMapExpandLayer``)."""
    b, d = x.shape
    if as_row:
        return jnp.tile(x[:, None, :], (1, num_filters, 1)).reshape(b, -1)
    return jnp.tile(x[:, :, None], (1, 1, num_filters)).reshape(b, -1)


@register_op("block_expand")
def block_expand(x, block_h: int, block_w: int, stride_h: int, stride_w: int,
                 pad_h: int = 0, pad_w: int = 0):
    """Image → sequence of flattened patches (``BlockExpandLayer``), NHWC in,
    [B, S, block_h*block_w*C] out (S = #patches, row-major)."""
    x = jnp.pad(x, [(0, 0), (pad_h, pad_h), (pad_w, pad_w), (0, 0)])
    patches = lax.conv_general_dilated_patches(
        jnp.moveaxis(x, -1, 1), (block_h, block_w), (stride_h, stride_w),
        padding="VALID")  # [N, C*bh*bw, OH, OW]
    n, f, oh, ow = patches.shape
    return jnp.moveaxis(patches.reshape(n, f, oh * ow), 1, 2)


@register_op("rotate")
def rotate(x, height: int, width: int):
    """Rotate flattened [B, H*W*C] feature maps 90° CCW (``RotateLayer``)."""
    b = x.shape[0]
    c = x.shape[1] // (height * width)
    img = x.reshape(b, height, width, c)
    return jnp.rot90(img, k=1, axes=(1, 2)).reshape(b, -1)


@register_op("switch_order")
def switch_order(x, to: str = "NHWC"):
    """NCHW↔NHWC (``SwitchOrderLayer``, ``paddle/function/SwitchOp``)."""
    if to == "NHWC":
        return jnp.moveaxis(x, 1, -1)
    return jnp.moveaxis(x, -1, 1)
