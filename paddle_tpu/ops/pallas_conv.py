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

**The 3×3 product (all kernels, :func:`_conv3x3_bands`).**  A grid step takes
``nb`` images ("arbitrary" semantics; pallas double-buffers the image
blocks).  Their operand — x, or the cotangent for backward-data — is
staged once into a zero-padded VMEM copy whose rows have a pitch P of
whole sublane tiles (the map's width rounded up), so tap (a, b) over a
band of map rows is one slice of the copy that reshapes to
``[nb·rows·P, C]`` without a relayout; the P − W columns past the map
come out of the product as rows that are dropped.  The nine taps are
multiplied in the dtype the weights arrive in (bfloat16 under the
default policy) and accumulate in float32: as nine products of K = C,
or, where C is narrower than the MXU, as one of K = 9·C (the nine
shifted views side by side on the lanes).  :func:`_conv_tile` picks the
form, ``nb`` and the band from the call's shapes so that a product
streams about :data:`_DOT_ROWS` rows past each weight tile with K
filling the MXU: several images a step where the map is small, bands of
rows where it is large, taps stacked where the channels are narrow.  For
the backward-data direction the spatially-flipped, I/O-transposed
weight ``wT[a, b] = w[2−a, 2−b].T`` stays resident in VMEM.

Shapes that don't tile (channels not a multiple of 64, no tile inside
the VMEM budget) dispatch to the plain ``conv2d`` + ``batch_norm``
composition in :mod:`paddle_tpu.ops.nn_ops` — same contract, same
results.  On non-TPU backends the kernel runs in Pallas interpret mode
so CPU tests exercise the exact dispatch used on hardware.
"""

from __future__ import annotations

from functools import partial as _partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import is_tpu, pallas_interpret
from ..observe import gauge
from . import kernels as K


def _vmem_limit() -> int:
    """Scoped VMEM the kernels ask Mosaic for: half the chip's VMEM, at
    most 64 MiB (as the routed experts' kernels ask on a v5e).  Where no
    TPU is attached (the interpreter, a compile for a described chip) a
    v5e's 128 MiB is assumed."""
    cap = pltpu.get_tpu_info().vmem_capacity_bytes if is_tpu() \
        else 128 << 20
    return min(64 << 20, cap // 2)


def _vmem_budget() -> int:
    """What the tile rule lets a grid step hold: three quarters of
    :func:`_vmem_limit`, leaving room for Mosaic's own temporaries."""
    return _vmem_limit() * 3 // 4


#: rows of the 3×3 product streamed past each weight tile: loading a
#: 128×128 tile into the MXU costs about as much as streaming 128 rows
#: through it, so a product of fewer rows mostly waits on its weights
_DOT_ROWS = 512

#: taps one product contracts, by contraction form
_TAPS = {"c": 1, "9c": 9}


class ConvTile(NamedTuple):
    """How a fused 3×3 kernel takes its product (:func:`_conv_tile`)."""
    nb: int      # images a grid step
    k: str       # contraction form: "c" (9 products), "9c" (1)
    rows: int    # map rows a product covers (a band)


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m


def _sublanes(itemsize: int) -> int:
    """Rows of one VMEM tile of that item size (8 f32, 16 bf16)."""
    return 32 // itemsize


def _pitch(w: int) -> int:
    """Row pitch of the float32 padded copy: the map's width in whole
    8-row tiles."""
    return _rup(w, 8)


def _lanes(c: int) -> int:
    return _rup(c, 128)


def _tile_bytes(t: ConvTile, h, w, ck, cn, isz, img) -> int:
    """VMEM a grid step holds at tile ``t``: the image blocks (``img``
    bytes an image over every streamed input and output as VMEM tiles
    them, two buffers each), the weights (two buffers), the padded copy,
    the float32 prologue, and one band's taps, their stacked operand and
    its float32 product and epilogue."""
    p = _pitch(w)
    m = t.nb * t.rows * p
    return (2 * t.nb * img
            + 2 * 9 * ck * _lanes(cn) * isz
            + t.nb * (h + 2) * (p + 8) * _lanes(ck) * 4
            + t.nb * h * _rup(w, 8) * _lanes(ck) * 4
            + m * ((9 + _TAPS[t.k]) * _lanes(ck) * isz
                   + 2 * _lanes(cn) * 4))


def _conv_tile(h: int, w: int, ck: int, cn: int, n: int, isz: int = 2,
               img: int = 0):
    """The tile of a fused 3×3 kernel over ``n`` maps of ``h × w``,
    contracting ``ck`` channels into ``cn`` (Cin → Cout forward, Cout →
    Cin backward-data), operands of ``isz`` bytes, ``img`` bytes an
    image streamed (:func:`_tile_bytes`): the nine taps stacked into one
    product where ``ck`` fills less than the MXU's 128 rows (at 128
    channels a stack measured level with nine products on a v5e, at 64
    a quarter faster forward, PERF.md §6); as many images a step (a
    divisor of ``n``) as keep the product within :data:`_DOT_ROWS` rows
    where one map is smaller, bands of rows of about that many where it
    is larger.  Where that does not fit :func:`_vmem_budget`, fewer
    images, narrower bands, then nine separate taps; None where nothing
    fits."""
    budget = _vmem_budget()
    k = "9c" if ck < 128 else "c"
    per_image = h * _pitch(w)
    if per_image >= _DOT_ROWS:
        nb, rows = 1, -(-h // -(-per_image // _DOT_ROWS))
    else:
        nb = max(d for d in range(1, _DOT_ROWS // per_image + 1)
                 if n % d == 0)
        rows = h
    nbs = [d for d in range(nb, 0, -1) if n % d == 0]
    bands = sorted({max(1, rows >> s) for s in range(rows.bit_length())},
                   reverse=True)
    for form in dict.fromkeys((k, "c")):
        for b in nbs:
            for r in bands:
                t = ConvTile(b, form, r)
                if _tile_bytes(t, h, w, ck, cn, isz, img) <= budget:
                    return t
    return None


def _img32(h, w, *widths) -> int:
    """Bytes an image over float32 blocks of these channel widths: what
    the gates assume, float32 being the widest operand a caller passes."""
    return h * _rup(w, 8) * sum(map(_lanes, widths)) * 4


def fused_ok(h: int, w: int, cin: int, cout: int) -> bool:
    """Mosaic tiling gate, checked on every backend so interpret-mode
    tests exercise the hardware dispatch.  Channels must land on the
    128-lane minor dimension in at most two tiles (multiples of 64 —
    covers ResNet-50's 3×3 family: 64/128/256/512); the backward-data
    kernel (dy, z in; dx, dz out) must find a tile at float32 operands,
    the widest any caller passes."""
    if cin % 64 or cout % 64 or h < 1 or w < 1:
        return False
    return _conv_tile(h, w, cout, cin, 1, 4,
                      _img32(h, w, cout, cout, cin, cout)) is not None


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
    :func:`fused_ok`; both kernels must find a tile at float32 operands
    (forward: z in, y out; backward: dy, z in, dz, x out)."""
    if cin % 64 or cout % 64 or h < 1 or w < 1:
        return False
    return (_conv_tile(h, w, cin, cout, 1, 4,
                       _img32(h, w, cin, cout)) is not None
            and _conv_tile(h, w, cout, cin, 1, 4,
                           _img32(h, w, cout, cin, cin, cin)) is not None)


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
    (dy, z2, z1) and writes (dz2, dz1, x1)."""
    return fused_fwd_ok(h, w, cin, cout) and _conv_tile(
        h, w, cout, cin, 1, 4,
        _img32(h, w, cout, cout, cin, cout, cin, cin)) is not None


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


# ------------------------------------------------------- the 3×3 product
def _conv3x3_bands(pad_s, x, w_ref, tile: ConvTile):
    """Inside a kernel: stage ``x`` (``[nb, H, W, C]``, any float dtype)
    into the zero-padded float32 copy ``pad_s`` and yield ``(i0, i1,
    acc)`` for each band of map rows, ``acc`` the float32 3×3 product
    over rows ``i0:i1``, ``[nb, i1 − i0, W, Cout]``.  ``w_ref`` holds the
    weights as ``[9·C, Cout]`` (HWIO reshaped), in the operands' dtype:
    each tap is cast to it as it is read.  (Staged in bfloat16 the copy
    costs more than it saves: Mosaic shifts packed rows by one sublane
    far more slowly than 32-bit ones, PERF.md §6.)"""
    nb, hh, ww, c = x.shape
    pitch = pad_s.shape[2] - 8

    @pl.when(pl.program_id(0) == 0)
    def _zero_borders():
        # the map is overwritten every step; the borders and the columns
        # past the map read as the SAME zero padding, zeroed once
        pad_s[...] = jnp.zeros_like(pad_s)

    pad_s[:, 1:hh + 1, 1:ww + 1, :] = x.astype(jnp.float32)
    g = _TAPS[tile.k]
    for i0 in range(0, hh, tile.rows):
        i1 = min(i0 + tile.rows, hh)
        m = nb * (i1 - i0) * pitch
        tap = lambda t: pad_s[:, t // 3 + i0:t // 3 + i1,
                              t % 3:t % 3 + pitch, :].reshape(m, c) \
            .astype(w_ref.dtype)
        acc = None
        for j in range(0, 9, g):
            lhs = tap(j) if g == 1 else jnp.concatenate(
                [tap(t) for t in range(j, j + g)], 1)
            part = jax.lax.dot_general(
                lhs, w_ref[j * c:(j + g) * c, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        yield i0, i1, acc.reshape(nb, i1 - i0, pitch, -1)[:, :, :ww, :]


def _affine_bwd(t, z, ci, relu):
    """The forward prologue x = act(A·z + C)'s backward on the
    cotangent ``t`` wrt x, float32: (dz, the recomputed x, dA, dC)."""
    z = z.astype(jnp.float32)
    u = ci[0] * z + ci[1]
    if relu:
        du = jnp.where(u > 0, t, 0.0)
        x = jnp.maximum(u, 0.0)
    else:
        du, x = t, u
    return (ci[0] * du, x, jnp.sum(z * du, axis=(0, 1, 2)),
            jnp.sum(du, axis=(0, 1, 2)))


def _fused_call(kernel, name, tile, operands, out_shape):
    """The ``pallas_call`` of a fused 3×3 kernel at ``tile``: 4-D
    operands and outputs move ``tile.nb`` images a grid step, 2-D ones
    (affine blocks, weights) stay resident.  The last operand is the
    weights, ``[9·C, Cout]``: the padded copy holds C channels."""
    n, h, w = operands[0].shape[:3]
    ck = operands[-1].shape[0] // 9

    def spec(shape):
        if len(shape) == 4:
            return pl.BlockSpec((tile.nb, h, w, shape[3]),
                                lambda i: (i, 0, 0, 0))
        return pl.BlockSpec(tuple(shape), lambda i: (0, 0))

    return pl.pallas_call(
        _partial(kernel, tile=tile),
        grid=(n // tile.nb,),
        in_specs=[spec(a.shape) for a in operands],
        out_specs=[spec(s.shape) for s in out_shape],
        out_shape=out_shape,
        scratch_shapes=[                    # the padded copy
            pltpu.VMEM((tile.nb, h + 2, _pitch(w) + 8, ck), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit()),
        interpret=pallas_interpret(),
        name=name,
    )(*operands)


def _tile_for(name, n, h, w, cin, cout, operands, out_shape):
    """The rule's tile for the call's shapes, told to the
    ``conv_bn_tile`` gauge.  The forward kernel contracts Cin, the
    backward-data ones Cout."""
    ck, cn = (cin, cout) if name == K.CONV_BN_FWD else (cout, cin)
    img = sum(h * _rup(w, _sublanes(a.dtype.itemsize))
              * _lanes(a.shape[3]) * a.dtype.itemsize
              for a in (*operands, *out_shape) if len(a.shape) == 4)
    tile = _conv_tile(h, w, ck, cn, n, operands[-1].dtype.itemsize, img)
    gauge("conv_bn_tile",
          "images a grid step of a fused 3x3 conv+BN kernel, by the "
          "contraction form and band _conv_tile chose from the call's "
          "shapes (trace-time)"
          ).set(tile.nb, kernel=name, h=str(h), w=str(w), cin=str(cin),
                cout=str(cout), k=tile.k, rows=str(tile.rows))
    return tile


def _flipped(w):
    """Backward-data weights ``[9·Cout, Cin]``: the spatial flip and I/O
    transpose of the forward HWIO weights."""
    cin, cout = w.shape[2:]
    return jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2).reshape(9 * cout, cin)


# ------------------------------------------------------------- dX kernel
def _dx_kernel(g_ref, z_ref, co_ref, wt_ref, dx_ref, dz_ref, pad_s, *,
               tile):
    """Form dz = A·dy + B·z + C in VMEM (float32; the affine
    coefficients mix magnitudes), write it out for the filter-grad conv,
    then run the 3×3 backward-data product on it."""
    co = co_ref[...]                                 # [8, Cout]
    dz = (co[0] * g_ref[...].astype(jnp.float32)
          + co[1] * z_ref[...].astype(jnp.float32) + co[2])
    dz_ref[...] = dz.astype(dz_ref.dtype)
    for i0, i1, acc in _conv3x3_bands(pad_s, dz, wt_ref, tile):
        dx_ref[:, i0:i1] = acc.astype(dx_ref.dtype)


def _dx_call(dy, z, coeffs, w, dx_dtype, dz_dtype):
    """dy, z: [N, H, W, Cout]; coeffs: [8, Cout] f32 (rows 0..2 =
    A/B/C, rest zero); w: [3, 3, Cin, Cout] forward HWIO weights.
    Returns (dx [N, H, W, Cin], dz [N, H, W, Cout])."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    operands = (dy, z, coeffs, _flipped(w))
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cin), dx_dtype),
        jax.ShapeDtypeStruct((n, h, ww, cout), dz_dtype),
    ]
    # the op: one 3×3 backward-data conv
    K.record_kernel_work(K.CONV_BN_DX, _conv_flops(n, h, ww, cin, cout),
                         operands, out_shape)
    tile = _tile_for(K.CONV_BN_DX, n, h, ww, cin, cout, operands,
                     out_shape)
    return _fused_call(_dx_kernel, K.CONV_BN_DX, tile, operands, out_shape)


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
def _fwd_kernel(z_ref, ci_ref, w_ref, o_ref, pad_s, *, tile, relu):
    """Form x = act(A·z + C) in VMEM from the upstream BN's folded
    per-channel affine (float32) and run the 3×3 forward product on it
    (weights resident) — the normalized activation never exists in
    HBM."""
    ci = ci_ref[...]                                 # [8, Cin]
    x = ci[0] * z_ref[...].astype(jnp.float32) + ci[1]
    if relu:
        x = jnp.maximum(x, 0.0)
    for i0, i1, acc in _conv3x3_bands(pad_s, x, w_ref, tile):
        o_ref[:, i0:i1] = acc.astype(o_ref.dtype)


def _fwd_call(z, ci, w, out_dtype, relu):
    """z: [N, H, W, Cin]; ci: [8, Cin] f32 (rows A, C); w: [3, 3, Cin,
    Cout] HWIO forward weights.  Returns conv(act(A·z+C), w)."""
    n, h, ww, cin = z.shape
    cout = w.shape[3]
    operands = (z, ci, w.reshape(9 * cin, cout))
    out_shape = [jax.ShapeDtypeStruct((n, h, ww, cout), out_dtype)]
    # the op: one 3×3 forward conv
    K.record_kernel_work(K.CONV_BN_FWD, _conv_flops(n, h, ww, cin, cout),
                         operands, out_shape)
    tile = _tile_for(K.CONV_BN_FWD, n, h, ww, cin, cout, operands,
                     out_shape)
    return _fused_call(_partial(_fwd_kernel, relu=relu), K.CONV_BN_FWD,
                       tile, operands, out_shape)[0]


# ----------------------------------------------------- forward backward
def _fwd_bwd_kernel(g_ref, z_ref, ci_ref, wt_ref, dz_ref, x_ref, dac_ref,
                    pad_s, *, tile, relu):
    """Backward of the affine(+ReLU)→conv forward: the 3×3 backward-data
    product over the cotangent (flipped weights), then the prologue's
    backward applied on-chip — du = mask·t, dz = A·du — while
    x = act(A·z + C) is RECOMPUTED from the raw residual z and written
    once for the XLA filter-grad conv.  dA/dC accumulate across the
    sequential grid directly in their constant-block output ref (the
    pallas_lstm dW idiom)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dac_ref[...] = jnp.zeros_like(dac_ref)

    ci = ci_ref[...]
    for i0, i1, t in _conv3x3_bands(pad_s, g_ref[...], wt_ref, tile):
        dz, x, da, dc = _affine_bwd(t, z_ref[:, i0:i1], ci, relu)
        dz_ref[:, i0:i1] = dz.astype(dz_ref.dtype)
        x_ref[:, i0:i1] = x.astype(x_ref.dtype)
        dac_ref[0] = dac_ref[0] + da
        dac_ref[1] = dac_ref[1] + dc


def _fwd_bwd_call(dy, z, ci, w, relu):
    """dy: [N, H, W, Cout] conv-output cotangent; z: [N, H, W, Cin] raw
    BN input; ci: [8, Cin]; w: [3, 3, Cin, Cout] forward weights.
    Returns (dz, x, dac[8, Cin] with rows dA/dC)."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    operands = (dy, z, ci, _flipped(w))
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cin), z.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z.dtype),
        jax.ShapeDtypeStruct((8, cin), jnp.float32),
    ]
    # the op: one 3×3 backward-data conv (x is recomputed, not work)
    K.record_kernel_work(K.CONV_BN_FWD_BWD,
                         _conv_flops(n, h, ww, cin, cout),
                         operands, out_shape)
    tile = _tile_for(K.CONV_BN_FWD_BWD, n, h, ww, cin, cout, operands,
                     out_shape)
    return _fused_call(_partial(_fwd_bwd_kernel, relu=relu),
                       K.CONV_BN_FWD_BWD, tile, operands, out_shape)


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
                      tile, relu):
    """BOTH affines in one backward-data pass (the composed fwd-fusion ×
    round-6 path): form dz2 = A₂·dy + B₂·z2 + C₂ on-chip (the BN2
    backward, exactly the round-6 input pipeline), run the 3×3
    backward-data product on it, then apply the forward prologue's
    backward on the result — du = mask·t, dz1 = A₁·du — recomputing
    x1 = act(A₁·z1 + C₁) for the filter grad, with dA₁/dC₁ accumulating
    in their constant-block output ref."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dac_ref[...] = jnp.zeros_like(dac_ref)

    co = co_ref[...]                                 # [8, Cout]
    dz2 = (co[0] * g_ref[...].astype(jnp.float32)
           + co[1] * z2_ref[...].astype(jnp.float32) + co[2])
    dz2_ref[...] = dz2.astype(dz2_ref.dtype)
    ci = ci_ref[...]                                 # [8, Cin]
    for i0, i1, t in _conv3x3_bands(pad_s, dz2, wt_ref, tile):
        dz1, x1, da, dc = _affine_bwd(t, z1_ref[:, i0:i1], ci, relu)
        dz1_ref[:, i0:i1] = dz1.astype(dz1_ref.dtype)
        x1_ref[:, i0:i1] = x1.astype(x1_ref.dtype)
        dac_ref[0] = dac_ref[0] + da
        dac_ref[1] = dac_ref[1] + dc


def _chain_bwd_call(dy, z2, co, z1, ci, w, relu):
    """Returns (dz2, dz1, x1, dac) — dz2 materialized for the XLA
    filter-grad conv, x1 recomputed for the same, dz1 for the upstream,
    dac rows = dA₁/dC₁."""
    n, h, ww, cout = dy.shape
    cin = w.shape[2]
    operands = (dy, z2, co, z1, ci, _flipped(w))
    out_shape = [
        jax.ShapeDtypeStruct((n, h, ww, cout), z2.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z1.dtype),
        jax.ShapeDtypeStruct((n, h, ww, cin), z1.dtype),
        jax.ShapeDtypeStruct((8, cin), jnp.float32),
    ]
    # the op: one 3×3 backward-data conv between two BN affines
    K.record_kernel_work(K.CONV_BN_CHAIN_BWD,
                         _conv_flops(n, h, ww, cin, cout),
                         operands, out_shape)
    tile = _tile_for(K.CONV_BN_CHAIN_BWD, n, h, ww, cin, cout, operands,
                     out_shape)
    return _fused_call(_partial(_chain_bwd_kernel, relu=relu),
                       K.CONV_BN_CHAIN_BWD, tile, operands, out_shape)


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
