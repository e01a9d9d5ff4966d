"""Fused conv + BatchNorm-affine Pallas TPU kernels (both directions).

The ResNet-class train step is HBM-bound, not MXU-bound (PERF.md §5:
BN's memory-bound passes lead the device time).  The
largest removable slice of that traffic is the seam between BatchNorm
and the convs on either side of it: XLA cannot fuse an elementwise
producer into a convolution operand (convs read their inputs from HBM).

**Backward half (round 6).**  The BN backward's apply pass

    dz = scale·inv · (dy − Σdy/N − x̂ · Σ(dy·x̂)/N)

materializes ``dz`` in HBM only for the conv backward-data and
backward-filter kernels to immediately re-read it.  The reference hit
the same wall on GPUs and solved it with fused cuDNN conv/BN entry
points (``hl_cuda_cudnn.cc`` / ``CudnnBatchNormLayer.cpp``); the TPU
analogue of that tier is this module.

Key identity: with A = scale·inv, B = −A·inv·Σ(dy·x̂)/N and
C = A·(inv·m·Σ(dy·x̂) − Σdy)/N (all per-channel scalars computed by one
reduction pass), the BN backward is the **per-channel affine**

    dz = A·dy + B·z + C

of two tensors already resident in HBM (the upstream cotangent dy and
the conv output z, which is saved for the BN backward anyway).  The
Pallas backward-data kernel below streams (dy, z) tiles through VMEM,
forms dz on-chip, and immediately runs the 3×3 backward-data matmuls on
it — writing dx *and* dz in the same pass so the filter-grad conv that
still runs under XLA reads a ready-made dz.  Per fused conv→BN pair
this removes one full read+write of an activation-sized tensor from the
step (the apply pass's dz store and the backward-data conv's dz load),
which is exactly the traffic class that bounds the step.

**Forward half (round 7).**  The forward pass pays the same seam tax in
the other direction: every BN normalize+scale+ReLU apply writes a full
activation tensor that the next conv immediately re-reads from HBM.
With A = scale·inv and C = bias − m·A (per-channel scalars from the
stats pass), the normalized activation is ``x = act(A·z + C)`` of the
raw conv output z already in HBM — so the forward conv kernel here
applies that affine (+ReLU) **in its input pipeline**, forming x
tile-by-tile in VMEM and never materializing it in HBM.  Its
``custom_vjp`` keeps the raw z as the residual and *recomputes* the
affine in the backward kernel (mask + x for the filter grad), and the
chain variant (``_chain_core``) composes the forward prologue with the
round-6 fused backward-data kernel so a BN→conv→BN sandwich runs both
affines through one backward kernel pass.

Kernel shape (all kernels): grid = (N,) with one image per step
("arbitrary" semantics, pallas double-buffers the streaming blocks).
The 3×3 stride-1 conv — forward or backward-data — is decomposed into
9 shifted [H·W, Cin] @ [Cin, Cout] (resp. [H·W, Cout] @ [Cout, Cin])
MXU matmuls over a zero-padded VMEM scratch tile — no halo exchange,
no [T, T]-style intermediate, one HBM read of each operand.  For the
backward-data direction the spatially-flipped, I/O-transposed weight
``wT[a, b] = w[2−a, 2−b].T`` stays resident in VMEM (≤ 9.4 MB f32 at
C=512, inside the 16 MB budget with the stage-4 7×7 tiles).

Shapes that don't tile (channels not a multiple of 64, VMEM overflow)
dispatch to the plain ``conv2d`` + ``batch_norm`` composition in
:mod:`paddle_tpu.ops.nn_ops` — same contract, same results.  On
non-TPU backends the kernel runs in Pallas interpret mode so CPU tests
exercise the exact dispatch used on hardware.
"""

from __future__ import annotations

from functools import partial as _partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import pallas_interpret
from . import kernels as K

# VMEM budget for the gate: tiles + resident weights must fit under the
# 16 MB scoped-vmem cap with headroom for double-buffering.
_VMEM_BUDGET = 12 * 1024 * 1024


def fused_ok(h: int, w: int, cin: int, cout: int) -> bool:
    """Mosaic tiling gate, checked on every backend so interpret-mode
    tests exercise the hardware dispatch.  Channels must land on the
    128-lane minor dimension in at most two tiles (multiples of 64 —
    covers ResNet-50's 3×3 family: 64/128/256/512); the per-image tile
    set (dy, z, dz f32, padded-dz scratch, dx accumulator) plus the
    resident flipped weight must fit the VMEM budget."""
    if cin % 64 or cout % 64 or h < 1 or w < 1:
        return False
    f32 = 4
    tile = h * w * (4 * cout + cin) * f32 \
        + (h + 2) * (w + 2) * cout * f32
    return tile + 9 * cout * cin * f32 <= _VMEM_BUDGET


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _geom3x3_ok(x_shape, w_shape, stride, padding, dilation, groups,
                data_format) -> bool:
    """Static geometry gate shared by the backward (round-6) and
    forward fusion paths: the 3×3 stride-1 SAME/pad-1 groupless NHWC
    family."""
    if data_format != "NHWC" or groups != 1:
        return False
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if tuple(w_shape[:2]) != (3, 3):
        return False
    if _pair(stride) != (1, 1) or _pair(dilation) != (1, 1):
        return False
    if isinstance(padding, str):
        if padding != "SAME":
            return False
    else:
        pads = [_pair(p) for p in padding] if not isinstance(padding, int) \
            else [(padding, padding)] * 2
        if pads != [(1, 1), (1, 1)]:
            return False
    return True


def fusable(x_shape, w_shape, stride, padding, dilation, groups,
            data_format) -> bool:
    """Full static dispatch gate for the fused conv→BN path: the 3×3
    stride-1 SAME/pad-1 grouped-less NHWC family whose shapes tile."""
    if not _geom3x3_ok(x_shape, w_shape, stride, padding, dilation,
                       groups, data_format):
        return False
    n, h, w_, _cin = x_shape
    return fused_ok(h, w_, int(w_shape[2]), int(w_shape[3]))


def fused_fwd_ok(h: int, w: int, cin: int, cout: int) -> bool:
    """Mosaic tiling gate for the FORWARD fused conv (affine+ReLU input
    pipeline) and its backward twin — same 64-multiple channel rule as
    :func:`fused_ok`; the VMEM estimate covers whichever of the two
    kernels' tile sets is larger (fwd: z + padded-x scratch + out acc;
    bwd: dy + padded-dy scratch + z/du/dz/x tiles + the dA/dC
    accumulator block) plus the resident weights."""
    if cin % 64 or cout % 64 or h < 1 or w < 1:
        return False
    f32 = 4
    fwd = h * w * (2 * cin + 2 * cout) * f32 \
        + (h + 2) * (w + 2) * cin * f32
    bwd = h * w * (4 * cin + 2 * cout) * f32 \
        + (h + 2) * (w + 2) * cout * f32 + 8 * cin * f32
    return max(fwd, bwd) + 9 * cin * cout * f32 <= _VMEM_BUDGET


def fusable_fwd(z_shape, w_shape, stride, padding, dilation, groups,
                data_format) -> bool:
    """Full static dispatch gate for the fused BN(+ReLU)→conv forward
    path (the 3×3 Pallas kernel; the 1×1 GEMM-prologue path has its own
    gate in :mod:`paddle_tpu.ops.nn_ops`)."""
    if not _geom3x3_ok(z_shape, w_shape, stride, padding, dilation,
                       groups, data_format):
        return False
    n, h, w_, _cin = z_shape
    return fused_fwd_ok(h, w_, int(w_shape[2]), int(w_shape[3]))


def fused_chain_ok(h: int, w: int, cin: int, cout: int) -> bool:
    """VMEM gate for the chain kernel (forward affine prologue × round-6
    BN-backward affine in ONE backward-data pass): its backward streams
    (dy, z2, z1) and writes (dz2, dz1, x1) with both affine blocks and
    the padded-dz2 scratch resident."""
    if not fused_fwd_ok(h, w, cin, cout):
        return False
    f32 = 4
    tile = h * w * (4 * cin + 3 * cout) * f32 \
        + (h + 2) * (w + 2) * cout * f32 + 8 * (cin + cout) * f32
    return tile + 9 * cin * cout * f32 <= _VMEM_BUDGET


def _conv3x3(x, w):
    """The forward this module's backward belongs to: 3×3 stride-1
    pad-1 NHWC/HWIO conv, stated exactly as ``nn_ops.conv2d`` lowers it
    so the fused op's forward is bit-identical to the unfused path."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
        dimension_numbers=dn)


def _conv_flops(n, h, w, cin, cout) -> float:
    """One direction (forward or backward-data) of a 3×3 stride-1 conv
    over [N, H, W]: what ``kernels.record_kernel_work`` is told."""
    return 2.0 * n * h * w * 9 * cin * cout


# ------------------------------------------------------------- dX kernel
def _dx_kernel(g_ref, z_ref, co_ref, wt_ref, dx_ref, dz_ref, pad_s, *,
               hh, ww):
    """One image per grid step: form dz = A·dy + B·z + C in VMEM, write
    it out for the filter-grad conv, then accumulate the 9 shifted
    matmuls of the 3×3 backward-data conv from the zero-padded scratch.
    All compute in f32 (the affine coefficients mix magnitudes; the MXU
    accumulates f32 natively)."""
    g = g_ref[0].astype(jnp.float32)                 # [H, W, Cout]
    z = z_ref[0].astype(jnp.float32)
    co = co_ref[...].astype(jnp.float32)             # [8, Cout]
    dz = co[0] * g + co[1] * z + co[2]               # per-channel affine
    dz_ref[0] = dz.astype(dz_ref.dtype)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero_borders():
        # interior is overwritten every step; borders must read as the
        # implicit SAME zero-padding and only need zeroing once
        pad_s[...] = jnp.zeros_like(pad_s)

    pad_s[1:hh + 1, 1:ww + 1, :] = dz
    wt = wt_ref[...].astype(jnp.float32)             # [3, 3, Cout, Cin]
    cin = wt.shape[-1]
    acc = jnp.zeros((hh * ww, cin), jnp.float32)
    for a in range(3):
        for b in range(3):
            sl = pad_s[a:a + hh, b:b + ww, :].reshape(hh * ww, -1)
            acc = acc + jax.lax.dot_general(
                sl, wt[a, b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    dx_ref[0] = acc.reshape(hh, ww, cin).astype(dx_ref.dtype)


def _dx_call(dy, z, coeffs, w, dx_dtype, dz_dtype):
    """dy, z: [N, H, W, Cout]; coeffs: [8, Cout] f32 (rows 0..2 =
    A/B/C, rest zero); w: [3, 3, Cin, Cout] forward HWIO weights.
    Returns (dx [N, H, W, Cin], dz [N, H, W, Cout])."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    # backward-data kernel: spatial flip + I/O transpose of the forward
    # weights (constant-folded outside the step loop by XLA)
    wt = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2)   # [3, 3, Cout, Cin]
    kernel = _partial(_dx_kernel, hh=h, ww=ww)
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cin), dx_dtype),
        jax.ShapeDtypeStruct((n, h, ww, cout), dz_dtype),
    ]
    # the op: one 3×3 backward-data conv
    K.record_kernel_work(K.CONV_BN_DX, _conv_flops(n, h, ww, cin, cout),
                         (dy, z, coeffs, wt), out_shape)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # dy
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # z
            pl.BlockSpec((8, cout), lambda i: (0, 0)),          # coeffs
            pl.BlockSpec((3, 3, cout, cin), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # dx
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # dz
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((h + 2, ww + 2, cout), jnp.float32),  # padded dz
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.CONV_BN_DX,
    )(dy, z, coeffs, wt)


# ------------------------------------------------------------ custom vjp
@_partial(jax.custom_vjp, nondiff_argnums=(5,))
def _conv_bn_core(x, w, cb, scale, bias, eps):
    """Training-mode conv(3×3, s1, p1) + per-batch BatchNorm, NHWC.
    x [N,H,W,Cin], w [3,3,Cin,Cout] HWIO, cb/scale/bias [Cout].
    Returns y only; the caller recomputes (m, v) for the running
    averages (XLA CSEs the conv and the reductions with the ones in
    here)."""
    (y, _res) = _core_fwd(x, w, cb, scale, bias, eps)
    return y


def _core_fwd(x, w, cb, scale, bias, eps):
    from .nn_ops import _bn_apply, _bn_stats

    z = _conv3x3(x, w) + cb.astype(x.dtype)
    m, v = _bn_stats(z, (0, 1, 2))
    inv = lax.rsqrt(v + eps)
    y = _bn_apply(z, scale, bias, m, inv, 3)
    return y, (x, w, z, cb, scale, m, inv)


def _core_bwd(eps, res, dy):
    """The fused backward.  One XLA reduction pass over (dy, z) yields
    Σdy and Σdy·x̂ (= dbias, dscale — the BN parameter grads); from
    those the per-channel affine coefficients of dz are scalars, and
    the Pallas kernel produces dx and dz in a single pass over HBM.
    The filter grad runs as XLA's standard backward-filter conv on the
    kernel's dz output; the conv-bias grad Σdz reduces to channel
    scalars analytically (A·Σdy + B·N·m + C·N — no tensor pass).
    Running-average buffers are stop-gradient side-channel state, as
    everywhere else in this codebase."""
    x, w, z, cb, scale, m, inv = res
    cout = z.shape[-1]
    shape = (1, 1, 1, cout)
    nelem = np.prod([z.shape[i] for i in (0, 1, 2)]).astype(np.float32)
    dy_f = dy.astype(jnp.float32)
    xhat = (z.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
    dbias = jnp.sum(dy_f, axis=(0, 1, 2))
    dscale = jnp.sum(dy_f * xhat, axis=(0, 1, 2))

    a_c = scale.astype(jnp.float32) * inv
    b_c = -a_c * inv * dscale / nelem
    c_c = a_c * (inv * m * dscale - dbias) / nelem
    coeffs = jnp.zeros((8, cout), jnp.float32) \
        .at[0].set(a_c).at[1].set(b_c).at[2].set(c_c)

    dx, dz = _dx_call(dy, z, coeffs, w, x.dtype, z.dtype)
    # filter grad: XLA's native backward-filter conv over the dz the
    # kernel just wrote (jax.vjp emits the canonical transpose conv)
    _, conv_vjp = jax.vjp(lambda w_: _conv3x3(x, w_), w)
    dw, = conv_vjp(dz)
    dcb = a_c * dbias + b_c * (nelem * m) + c_c * nelem
    return (dx, dw.astype(w.dtype), dcb.astype(cb.dtype),
            dscale.astype(scale.dtype), dbias.astype(scale.dtype))


def _core_fwd_rule(x, w, cb, scale, bias, eps):
    y, res = _core_fwd(x, w, cb, scale, bias, eps)
    return y, res


_conv_bn_core.defvjp(_core_fwd_rule, _core_bwd)


# ====================================================== forward fusion
def _pack_affine(a, c, n):
    """[8, n] f32 block (8 sublanes) carrying the per-channel affine:
    row 0 = scale A, row 1 = offset C, rest zero."""
    return jnp.zeros((8, n), jnp.float32) \
        .at[0].set(a.astype(jnp.float32)) \
        .at[1].set(c.astype(jnp.float32))


# ------------------------------------------------------ forward kernel
def _fwd_kernel(z_ref, ci_ref, w_ref, o_ref, pad_s, *, hh, ww, relu):
    """One image per grid step: form x = act(A·z + C) in VMEM from the
    upstream BN's folded per-channel affine, stage it into the
    zero-padded scratch, and run the 3×3 stride-1 forward conv as 9
    shifted [H·W, Cin] @ [Cin, Cout] MXU matmuls (weights resident) —
    the normalized activation never exists in HBM."""
    z = z_ref[0].astype(jnp.float32)                 # [H, W, Cin]
    ci = ci_ref[...].astype(jnp.float32)             # [8, Cin]
    x = ci[0] * z + ci[1]
    if relu:
        x = jnp.maximum(x, 0.0)

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero_borders():
        # interior is overwritten every step; borders must read as the
        # implicit SAME zero-padding and only need zeroing once
        pad_s[...] = jnp.zeros_like(pad_s)

    pad_s[1:hh + 1, 1:ww + 1, :] = x
    w = w_ref[...].astype(jnp.float32)               # [3, 3, Cin, Cout]
    cout = w.shape[-1]
    acc = jnp.zeros((hh * ww, cout), jnp.float32)
    for a in range(3):
        for b in range(3):
            sl = pad_s[a:a + hh, b:b + ww, :].reshape(hh * ww, -1)
            acc = acc + jax.lax.dot_general(
                sl, w[a, b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(hh, ww, cout).astype(o_ref.dtype)


def _fwd_call(z, ci, w, out_dtype, relu):
    """z: [N, H, W, Cin]; ci: [8, Cin] f32 (rows A, C); w: [3, 3, Cin,
    Cout] HWIO forward weights.  Returns conv(act(A·z+C), w)."""
    n, h, ww, cin = z.shape
    cout = w.shape[3]
    kernel = _partial(_fwd_kernel, hh=h, ww=ww, relu=relu)
    out_shape = jax.ShapeDtypeStruct((n, h, ww, cout), out_dtype)
    # the op: one 3×3 forward conv
    K.record_kernel_work(K.CONV_BN_FWD, _conv_flops(n, h, ww, cin, cout),
                         (z, ci, w), (out_shape,))
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # z
            pl.BlockSpec((8, cin), lambda i: (0, 0)),            # affine
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((h + 2, ww + 2, cin), jnp.float32),   # padded x
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.CONV_BN_FWD,
    )(z, ci, w)


# ----------------------------------------------------- forward backward
def _fwd_bwd_kernel(g_ref, z_ref, ci_ref, wt_ref, dz_ref, x_ref, dac_ref,
                    pad_s, *, hh, ww, relu):
    """Backward of the affine(+ReLU)→conv forward: the 3×3 backward-data
    matmuls over the zero-padded cotangent (flipped weights), then the
    prologue's backward applied on-chip — du = mask·t, dz = A·du — while
    x = act(A·z + C) is RECOMPUTED from the raw residual z and written
    once for the XLA filter-grad conv.  dA/dC accumulate across the
    sequential grid directly in their constant-block output ref (the
    pallas_lstm dW idiom)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        pad_s[...] = jnp.zeros_like(pad_s)
        dac_ref[...] = jnp.zeros_like(dac_ref)

    g = g_ref[0].astype(jnp.float32)                 # [H, W, Cout]
    pad_s[1:hh + 1, 1:ww + 1, :] = g
    wt = wt_ref[...].astype(jnp.float32)             # [3, 3, Cout, Cin]
    cin = wt.shape[-1]
    acc = jnp.zeros((hh * ww, cin), jnp.float32)
    for a in range(3):
        for b in range(3):
            sl = pad_s[a:a + hh, b:b + ww, :].reshape(hh * ww, -1)
            acc = acc + jax.lax.dot_general(
                sl, wt[a, b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    t = acc.reshape(hh, ww, cin)                     # cotangent wrt x
    z = z_ref[0].astype(jnp.float32)
    ci = ci_ref[...].astype(jnp.float32)
    u = ci[0] * z + ci[1]
    if relu:
        du = jnp.where(u > 0, t, 0.0)
        x = jnp.maximum(u, 0.0)
    else:
        du, x = t, u
    dz_ref[0] = (ci[0] * du).astype(dz_ref.dtype)
    x_ref[0] = x.astype(x_ref.dtype)
    dac_ref[0] = dac_ref[0] + jnp.sum(z * du, axis=(0, 1))
    dac_ref[1] = dac_ref[1] + jnp.sum(du, axis=(0, 1))


def _fwd_bwd_call(dy, z, ci, w, relu):
    """dy: [N, H, W, Cout] conv-output cotangent; z: [N, H, W, Cin] raw
    BN input; ci: [8, Cin]; w: [3, 3, Cin, Cout] forward weights.
    Returns (dz, x, dac[8, Cin] with rows dA/dC)."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    wt = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2)   # [3, 3, Cout, Cin]
    kernel = _partial(_fwd_bwd_kernel, hh=h, ww=ww, relu=relu)
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cin), z.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z.dtype),
        jax.ShapeDtypeStruct((8, cin), jnp.float32),
    ]
    # the op: one 3×3 backward-data conv (x is recomputed, not work)
    K.record_kernel_work(K.CONV_BN_FWD_BWD,
                         _conv_flops(n, h, ww, cin, cout),
                         (dy, z, ci, wt), out_shape)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # dy
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # z
            pl.BlockSpec((8, cin), lambda i: (0, 0)),            # affine
            pl.BlockSpec((3, 3, cout, cin), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # dz
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # x
            pl.BlockSpec((8, cin), lambda i: (0, 0)),             # dA/dC
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((h + 2, ww + 2, cout), jnp.float32),  # padded dy
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.CONV_BN_FWD_BWD,
    )(dy, z, ci, wt)


# --------------------------------------------- standalone forward core
@_partial(jax.custom_vjp, nondiff_argnums=(4,))
def _affine_conv_core(z, a, c, w, relu):
    """y = conv3×3(act(a·z + c), w) with the affine applied in the VMEM
    input pipeline.  z [N,H,W,Cin]; a/c [Cin] f32 (the upstream BN's
    folded scale/offset); w [3,3,Cin,Cout] HWIO."""
    return _fwd_call(z, _pack_affine(a, c, z.shape[-1]), w, z.dtype, relu)


def _affine_core_fwd(z, a, c, w, relu):
    # residuals are the RAW z (+ the affine scalars): x is recomputed in
    # the backward kernel, never saved — saving it would re-spend the
    # HBM pass the fusion exists to remove
    y = _fwd_call(z, _pack_affine(a, c, z.shape[-1]), w, z.dtype, relu)
    return y, (z, a, c, w)


def _affine_core_bwd(relu, res, dy):
    z, a, c, w = res
    ci = _pack_affine(a, c, z.shape[-1])
    dz, x, dac = _fwd_bwd_call(dy, z, ci, w, relu)
    # filter grad: XLA's native backward-filter conv over the x the
    # kernel just recomputed (jax.vjp emits the canonical transpose)
    _, conv_vjp = jax.vjp(lambda w_: _conv3x3(x, w_), w)
    dw, = conv_vjp(dy.astype(x.dtype))
    return (dz, dac[0].astype(a.dtype), dac[1].astype(c.dtype),
            dw.astype(w.dtype))


_affine_conv_core.defvjp(_affine_core_fwd, _affine_core_bwd)


# ------------------------------------------------- chain backward kernel
def _chain_bwd_kernel(g_ref, z2_ref, co_ref, z1_ref, ci_ref, wt_ref,
                      dz2_ref, dz1_ref, x1_ref, dac_ref, pad_s, *,
                      hh, ww, relu):
    """BOTH affines in one backward-data pass (the composed fwd-fusion ×
    round-6 path): form dz2 = A₂·dy + B₂·z2 + C₂ on-chip (the BN2
    backward, exactly the round-6 input pipeline), run the 9 shifted
    backward-data matmuls on it, then apply the forward prologue's
    backward on the result — du = mask·t, dz1 = A₁·du — recomputing
    x1 = act(A₁·z1 + C₁) for the filter grad, with dA₁/dC₁ accumulating
    in their constant-block output ref."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        pad_s[...] = jnp.zeros_like(pad_s)
        dac_ref[...] = jnp.zeros_like(dac_ref)

    g = g_ref[0].astype(jnp.float32)                 # [H, W, Cout]
    z2 = z2_ref[0].astype(jnp.float32)
    co = co_ref[...].astype(jnp.float32)             # [8, Cout]
    dz2 = co[0] * g + co[1] * z2 + co[2]             # BN2 backward affine
    dz2_ref[0] = dz2.astype(dz2_ref.dtype)

    pad_s[1:hh + 1, 1:ww + 1, :] = dz2
    wt = wt_ref[...].astype(jnp.float32)             # [3, 3, Cout, Cin]
    cin = wt.shape[-1]
    acc = jnp.zeros((hh * ww, cin), jnp.float32)
    for a in range(3):
        for b in range(3):
            sl = pad_s[a:a + hh, b:b + ww, :].reshape(hh * ww, -1)
            acc = acc + jax.lax.dot_general(
                sl, wt[a, b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    t = acc.reshape(hh, ww, cin)                     # cotangent wrt x1
    z1 = z1_ref[0].astype(jnp.float32)
    ci = ci_ref[...].astype(jnp.float32)             # [8, Cin]
    u = ci[0] * z1 + ci[1]
    if relu:
        du = jnp.where(u > 0, t, 0.0)
        x1 = jnp.maximum(u, 0.0)
    else:
        du, x1 = t, u
    dz1_ref[0] = (ci[0] * du).astype(dz1_ref.dtype)
    x1_ref[0] = x1.astype(x1_ref.dtype)
    dac_ref[0] = dac_ref[0] + jnp.sum(z1 * du, axis=(0, 1))
    dac_ref[1] = dac_ref[1] + jnp.sum(du, axis=(0, 1))


def _chain_bwd_call(dy, z2, co, z1, ci, w, relu):
    """Returns (dz2, dz1, x1, dac) — dz2 materialized for the XLA
    filter-grad conv, x1 recomputed for the same, dz1 for the upstream,
    dac rows = dA₁/dC₁."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    wt = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2)
    kernel = _partial(_chain_bwd_kernel, hh=h, ww=ww, relu=relu)
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cout), z2.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z1.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z1.dtype),
        jax.ShapeDtypeStruct((8, cin), jnp.float32),
    ]
    # the op: one 3×3 backward-data conv between two BN affines
    K.record_kernel_work(K.CONV_BN_CHAIN_BWD,
                         _conv_flops(n, h, ww, cin, cout),
                         (dy, z2, co, z1, ci, wt), out_shape)
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # dy
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # z2
            pl.BlockSpec((8, cout), lambda i: (0, 0)),             # BN2
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # z1
            pl.BlockSpec((8, cin), lambda i: (0, 0)),          # prologue
            pl.BlockSpec((3, 3, cout, cin), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),  # dz2
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # dz1
            pl.BlockSpec((1, h, ww, cin), lambda i: (i, 0, 0, 0)),   # x1
            pl.BlockSpec((8, cin), lambda i: (0, 0)),             # dA/dC
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((h + 2, ww + 2, cout), jnp.float32),  # padded dz2
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.CONV_BN_CHAIN_BWD,
    )(dy, z2, co, z1, ci, wt)


# ------------------------------------------------------------ chain core
@_partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _chain_core(z1, a1, c1, w, cb, scale, bias, eps, relu):
    """Training-mode act(a1·z1 + c1) → conv(3×3, s1, p1) + cb →
    per-batch BatchNorm, NHWC — the round-6 conv→BN pair with the
    upstream BN's affine(+ReLU) streamed through its input pipeline.
    Returns (y, m, v); the m/v cotangents are dropped in the backward
    (running-average side-channel state with stop-gradient semantics,
    as everywhere else in this codebase)."""
    out, _res = _chain_fwd(z1, a1, c1, w, cb, scale, bias, eps, relu)
    return out


def _chain_fwd(z1, a1, c1, w, cb, scale, bias, eps, relu):
    from .nn_ops import _bn_apply, _bn_stats

    z2 = _fwd_call(z1, _pack_affine(a1, c1, z1.shape[-1]), w, z1.dtype,
                   relu) + cb.astype(z1.dtype)
    m, v = _bn_stats(z2, (0, 1, 2))
    inv = lax.rsqrt(v + eps)
    y = _bn_apply(z2, scale, bias, m, inv, 3)
    return (y, m, v), (z1, a1, c1, w, cb, scale, m, inv, z2)


def _chain_core_fwd_rule(z1, a1, c1, w, cb, scale, bias, eps, relu):
    return _chain_fwd(z1, a1, c1, w, cb, scale, bias, eps, relu)


def _chain_core_bwd(eps, relu, res, cts):
    """One XLA reduction pass over (dy, z2) yields the BN2 parameter
    grads and the dz2 affine scalars (exactly round-6's `_core_bwd`);
    the chain kernel then produces dz2, dz1, x1 and the prologue's
    dA₁/dC₁ in a single pass over HBM.  The filter grad runs as XLA's
    backward-filter conv over (x1, dz2); the conv-bias grad Σdz2
    reduces analytically."""
    dy, _dm, _dv = cts
    z1, a1, c1, w, cb, scale, m, inv, z2 = res
    cout = z2.shape[-1]
    shape = (1, 1, 1, cout)
    nelem = np.prod([z2.shape[i] for i in (0, 1, 2)]).astype(np.float32)
    dy_f = dy.astype(jnp.float32)
    xhat = (z2.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
    dbias = jnp.sum(dy_f, axis=(0, 1, 2))
    dscale = jnp.sum(dy_f * xhat, axis=(0, 1, 2))

    a_c = scale.astype(jnp.float32) * inv
    b_c = -a_c * inv * dscale / nelem
    c_c = a_c * (inv * m * dscale - dbias) / nelem
    co = jnp.zeros((8, cout), jnp.float32) \
        .at[0].set(a_c).at[1].set(b_c).at[2].set(c_c)

    ci = _pack_affine(a1, c1, z1.shape[-1])
    dz2, dz1, x1, dac = _chain_bwd_call(dy, z2, co, z1, ci, w, relu)
    _, conv_vjp = jax.vjp(lambda w_: _conv3x3(x1, w_), w)
    dw, = conv_vjp(dz2)
    dcb = a_c * dbias + b_c * (nelem * m) + c_c * nelem
    return (dz1, dac[0].astype(a1.dtype), dac[1].astype(c1.dtype),
            dw.astype(w.dtype), dcb.astype(cb.dtype),
            dscale.astype(scale.dtype), dbias.astype(scale.dtype))


_chain_core.defvjp(_chain_core_fwd_rule, _chain_core_bwd)
